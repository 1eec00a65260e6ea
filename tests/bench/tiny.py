"""The benchmark's cells at a size a CPU test run can hold: the same
configuration files, traffic and limits, with every width and count cut as
the family's reference module says (its ``TINY``)."""

import _paths  # noqa: F401
from bench import harness


def tiny_cell(workload: str) -> dict:
    """``harness.resolve``'s result for ``workload``, cut to a tiny size."""
    r = harness.resolve(harness.load_manifest(), workload)
    tiny = harness.family_module(r["spec"]).TINY
    r["spec"].update(tiny["spec"])
    r["spec"]["program"]["fields"].update(tiny["program"])
    r["traffic"].update(tiny["traffic"])
    return r

"""The benchmark's cells at a size a CPU test run can hold: the same
configuration files, traffic and limits, with every width and count cut."""

import _paths  # noqa: F401
from bench import harness

DECODER = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=500, embedding_rows=512)
DECODER_PROGRAM = dict(d_model=64, d_ff=128, n_layers=2, n_heads=4,
                       n_kv_heads=2, head_dim=16, vocab=500)
ENCDEC = dict(d_model=64, d_ff=128, num_heads=4, d_k=16, d_v=16,
              vocab_size=500, embedding_rows=512, num_encoder_layers=2,
              num_decoder_layers=2)
ENCDEC_PROGRAM = dict(d_model=64, d_ff=128, n_layers=2, encoder_layers=2,
                      n_heads=4, n_kv_heads=4, head_dim=16, vocab=500)


def tiny_cell(workload: str) -> dict:
    """``harness.resolve``'s result for ``workload``, cut to a tiny size."""
    r = harness.resolve(harness.load_manifest(), workload)
    spec, mix = r["spec"], r["traffic"]
    if spec["family"] == "decoder":
        spec.update(DECODER)
        spec["program"]["fields"].update(DECODER_PROGRAM)
        mix.update(seq_len=32, batch_per_worker=2, distinct_batches=4)
    else:
        spec.update(ENCDEC)
        spec["program"]["fields"].update(ENCDEC_PROGRAM)
        mix.update(seq_len=16, source_len=16, batch_per_worker=4,
                   distinct_batches=4)
    return r

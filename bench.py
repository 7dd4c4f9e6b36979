"""Four names the benchmark's files still cite, and nothing else.

``benchmark/run.py`` is the repo's benchmark (``BENCHMARK.json``; README,
"Benchmarks").  This file is what is left of the script it replaced:
``benchmark/configs/flagship-l4-*.json`` give ``MFU_CFG`` and ``MFU_BATCH``
as the flagship's ``source``, ``benchmark/families/flagship/needs.json``
names ``MFU_CFG`` for its widths, ``benchmark/lib/peaks.json`` says it
agrees with ``CHIP_PEAKS``, and ``benchmark/families/flagship/counts.py``
counts as ``_transformer_train_flops_per_step`` does.  Nothing imports
it.  A ``benchmark`` PR re-points the four citations at the family's own
files, then this file goes (ROADMAP D1).
"""

# The repo's flagship GPT-style LM at its own widths (learned positions,
# tied head, bf16 compute).  The benchmark's cells run 4 of the 8 layers.
MFU_CFG = dict(vocab=8192, d_model=2048, n_heads=16, n_layers=8,
               d_ff=8192, max_seq=2048, attn_impl="flash")
MFU_BATCH = 4   # sequences of max_seq a worker a step

# Published per-chip peaks, keyed by jax's ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).  A kind
# that is not in the table is an error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def _transformer_train_flops_per_step(cfg, batch, seq):
    """Standard 6*N*T + attention-matmul term (12*L*T*seq*d_model*3 for
    fwd+bwd), counting the train step (fwd + 2x bwd)."""
    n_params = (cfg["vocab"] * cfg["d_model"]          # embed (tied head)
                + cfg["max_seq"] * cfg["d_model"]      # pos
                + cfg["n_layers"] * 12 * cfg["d_model"] ** 2)
    tokens = batch * seq
    dense = 6.0 * n_params * tokens
    attn = 12.0 * cfg["n_layers"] * tokens * seq * cfg["d_model"]
    return dense + attn, n_params

"""TPU Pallas kernels for the compute hot-spots:

- flash_attention : Nougat/LM attention (the ViT inference hot loop)
- budget_route    : AdaParse's fused alpha-budget select+compact dispatch
- ngram_score     : fused n-gram BLEU (the quality probe's scorer)
- fast_features   : fused prepare stage (CLS-I features + LLM tokens)
- segment_mm      : GNN fused edge-GEMM + segment scatter
- embedding_bag   : recsys fused gather + weighted reduce

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (public
jit wrapper w/ backend dispatch), ref.py (exact host oracle), and —
where a block size is worth sweeping — autotune.py on the shared
``autotune_common`` harness, with winners persisted fleet-wide through
``tuning_store`` (``serve.py --tuning-dir``).
Every kernel takes ``interpret`` with no default: the ops pass
``interpret=(backend != "tpu")``, CPU tests pass ``interpret=True``, and
tests/test_tpu_compile.py compiles the main-path kernels
(budget_route, fast_features, ngram_score) for a described v5e chip.
"""

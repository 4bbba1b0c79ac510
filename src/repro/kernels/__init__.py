"""TPU Pallas kernels for the compute hot-spots:

- flash_attention : causal/windowed LM attention; runs on no parsing
                    path (Nougat uses decode_attention)
- encoder_attention: the router encoder's bidirectional attention in
                    the route step (a whole row of keys in VMEM)
- decode_attention : Nougat's greedy decode step, one query row per page
                    slot, reading the K/V of live slots only
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
(budget_route, encoder_attention, decode_attention, fast_features,
ngram_score) for a described v5e chip.
"""

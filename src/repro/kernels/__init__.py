"""Pallas TPU kernels for the perf-critical compute paths.

Each subpackage: kernel.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd wrapper + layout adaptation + impl selection),
ref.py (pure-jnp oracle the tests sweep against in interpret mode).

flash_attention   blocked online-softmax fwd and FlashAttention-2 bwd
                  (custom_vjp); causal, GQA, traced sliding windows
                  (gemma3's per-layer scan), block skipping
decode_attention  single-token decode vs long KV caches; length + window
                  masking; sequential split-K analogue with VMEM scratch
ssd_scan          Mamba-2 chunked state-space dual scan (zamba2 backbone)
rwkv6             RWKV-6 WKV recurrence, log-space pairwise-decay chunking
                  with exact state carry (overflow-safe for any w)

The paper itself has no kernel-level contribution (its layer is the
cluster runtime); these are the substrate a production framework needs,
selected per-arch via cfg.attn_impl / ssm_impl / rwkv_impl = "pallas";
full attention also takes its kernel under attn_impl "auto" (the default)
wherever the program compiles for a TPU and the shapes tile.
"""

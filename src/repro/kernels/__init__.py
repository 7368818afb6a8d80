"""Pallas kernels for the compute hot-spots.  ``interpret`` defaults are
platform-gated (compiled on TPU, interpret where Pallas lacks a
compiled lowering for these kernel bodies — see
``repro.kernels.compose.default_interpret``):

  compose             the paper's neural-composition product (Eq. 4),
                      batched over an optional leading client axis
  rank_dense_apply    fused rank-space factor application with a
                      rank-space custom_vjp backward
  conv_rank_apply     fused conv rank path: basis conv (I→R) +
                      coefficient contraction (R→pO) in one kernel,
                      rank-space backward; on CPU/GPU the forward is an
                      equivalent fused XLA formulation
  compose_dense_apply compose+apply fusion for materialize-path dense
                      layers — the p-width weight is built in
                      VMEM/registers and consumed in the same kernel
  flash_attention     blockwise streaming-softmax attention (prefill/train)
  decode_attention    one-token GQA over a long KV cache (decode shapes)
  ssd_chunk           Mamba2 SSD intra-chunk block (SSM/hybrid archs)
  rmsnorm             fused row-tiled normalisation

``ops`` holds the jit'd public wrappers; ``ref`` the pure-jnp oracles the
sweep tests assert against (tests/test_kernels.py).

Audit note: on the engine's path are compose / rank_dense_apply /
conv_rank_apply / compose_dense_apply (``forward_impl`` dispatch) and
decode_attention (``repro.fl.transformer.greedy_decode``).  The
engine's transformer trains through the jnp
``repro.models.attention.flash_attention``, not the Pallas flash kernel
here; flash_attention, ssd_chunk and rmsnorm are oracle-tested but on no
engine path.  The five on the path compile for a TPU v5e
(tests/test_tpu_compile.py) and run there in ``chip_smoke.py``.
"""

from repro.kernels import ops, ref  # noqa: F401

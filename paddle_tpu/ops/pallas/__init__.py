"""Pallas TPU kernels — the hand-fused hot path (SURVEY.md §7 L8').

Capability mirror of the reference's hand-fused CUDA kernels
(operators/fused/multihead_matmul_op.cu, fused_embedding_eltwise_layernorm,
math/bert_encoder_functor.cu) and fused optimizer passes
(ir/fuse_optimizer_ops_pass/), re-designed as Pallas TPU kernels:

* flash_attention — blockwise online-softmax attention (fwd + bwd kernels),
* layer_norm      — fused row-normalisation,
* int8_gemm       — weight-only int8 MXU GEMM, dequant+bias+act fused
                    into the matmul epilogue (serving hot path),
* paged_attention — decode-step attention that walks the KV page table
                    directly (serving/kv_cache.py layout); paged_gqa_attention
                    for grouped heads, windows and rings; paged_mla_attention
                    over latent pages and latent rings (absorbed latent
                    attention),
* mla_prefill_attention — whole-prompt causal latent attention in its
                    expanded form on the layer's own [S, heads x width]
                    arrays: a step is one block pair of the causal
                    triangle for several heads, scores kept in VMEM, only
                    the diagonal masked (and attended in sub-blocks);
                    grouped heads on fewer K/V heads, and with a window
                    the band's block pairs alone,
* grouped_swiglu  — the routed layer's held experts over rows sorted by
                    expert: the hit experts' weight blocks streamed once,
                    gate, up, silu(g) * u and down under each block;
                    grouped_swiglu_bwd its backward for the trainer, two
                    kernels over the same rows (the rows' gradients, then
                    the three matrices'),
* routed_combine  — the same layer's sorted rows weighed and summed into
                    their tokens: a token tile's contiguous runs staged
                    by DMA, the sum a one-hot product in float32;
                    routed_spread its transpose over the same runs: a
                    token tile's rows to their sorted places, forward,
                    and the backward's cotangent rows with their weighed
                    copy, each rounded once.
* grouped_polyglu — (in grouped_swiglu.py) the same stream of weight
                    blocks for experts whose activation has a ROW
                    statistic (PolyNorm): gate and up of all of F kept in
                    VMEM, the statistics, then the down blocks,
* mhc_mix         — a residual path of several streams around a sublayer:
                    mhc_pre reads a token tile's streams once (norm, the
                    maps' logits, sigmoid and 20 Sinkhorn iterations by
                    lane rotations, the mixed input), mhc_post reads
                    streams and output once and writes the streams once.
* draft_tail      — what a drafting decode step does after its head
                    products (serving/decode.py draft_step): draft_verify
                    reads a slot's two logits rows and q's row at the slot
                    once and gives speculative sampling's one or two
                    tokens; draft_next writes the next q in place at the
                    slot and draws the draft; the two-level inverse CDF
                    by log-step scans.

Mode selection (``kernel_mode()``):
  'tpu'       compiled Pallas on a real TPU backend,
  'interpret' pallas interpreter (CPU tests validate kernels bit-for-bit
              against the jnp references),
  'off'       pure-jnp reference (XLA still fuses well; default on CPU).
Env override: PT_PALLAS=off|interpret|auto.

Mosaic kernels cannot be partitioned by XLA ("wrap the call in a
shard_map"): a step that jit partitions itself over a multi-device mesh
traces inside ``auto_partitioned()``, where mode 'tpu' reads 'off' and
flash attention takes its XLA route. Programs that run under shard_map
(explicit collectives) see per-shard shapes and keep their kernels.
"""

from __future__ import annotations

import contextlib
import os
import threading

_trace_scope = threading.local()


@contextlib.contextmanager
def auto_partitioned():
    """Trace scope of a program XLA partitions itself (core/executor.py's
    jit-with-shardings path over more than one device)."""
    prior = getattr(_trace_scope, "auto_partitioned", False)
    _trace_scope.auto_partitioned = True
    try:
        yield
    finally:
        _trace_scope.auto_partitioned = prior


def _requested_mode() -> str:
    env = os.environ.get("PT_PALLAS", "auto").lower()
    if env in ("off", "0", "false"):
        return "off"
    if env == "interpret":
        return "interpret"
    import jax

    # a backend that fails to initialise is an error, not mode 'off'
    return "tpu" if jax.default_backend() == "tpu" else "off"


def mosaic_withheld() -> bool:
    """True where kernel_mode() reads 'off' ONLY because the trace is
    auto-partitioned: compiled kernels were wanted and cannot be had."""
    return getattr(_trace_scope, "auto_partitioned", False) \
        and _requested_mode() == "tpu"


def kernel_mode() -> str:
    return "off" if mosaic_withheld() else _requested_mode()


def use_pallas() -> bool:
    return kernel_mode() in ("tpu", "interpret")


def interpret_mode() -> bool:
    return kernel_mode() == "interpret"


def kernels_fingerprint() -> str:
    """Mode + kernel-geometry fingerprint for compile-cache keys: a
    PT_PALLAS flip or a tile/chunk-constant change mid-process must
    RECOMPILE (the lowering changed), not reuse a stale entry. Named
    'pallas_kernels' in the executor's recompile-cause diagnostics and
    the decode engine's cost-capture keys."""
    from .draft_tail import draft_tail_fingerprint
    from .int8_gemm import int8_gemm_fingerprint
    from .paged_attention import paged_attn_fingerprint

    return (f"{kernel_mode()}|{int8_gemm_fingerprint()}"
            f"|{paged_attn_fingerprint()}|{draft_tail_fingerprint()}")


from .flash_attention import flash_attention  # noqa: E402,F401
from .layer_norm import fused_layer_norm  # noqa: E402,F401
from .int8_gemm import int8_weight_only_gemm  # noqa: E402,F401
from .paged_attention import paged_decode_attention  # noqa: E402,F401

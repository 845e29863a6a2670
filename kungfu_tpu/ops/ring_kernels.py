"""Hand-scheduled Pallas TPU ring kernels — the DMA data plane.

Kernel *bodies* for the ring reduce-scatter / all-gather pair and their
fused-codec variants; the public wrappers (padding, tiling, fallback,
shard_map plumbing) live in ops/pallas_collectives.py.  Everything here is
the `make_async_remote_copy` + DMA-semaphore pattern (SNIPPETS.md [1]-[3],
docs.jax.dev distributed Pallas guide):

  schedule   the standard 2(n-1)-hop ring split into an RS kernel and an
             AG kernel.  At RS step s, rank d sends the partial sum for
             chunk (d-s-1) mod n to its right neighbor and receives the
             partial for chunk (d-s-2) mod n from its left; after n-1
             steps rank d holds the complete chunk d — matching
             `lax.psum_scatter(..., scatter_dimension=0)` ownership.
  slots      every hop lands in its OWN comm slot (slot s for step s), so
             no incoming DMA can ever clobber bytes a slower rank has not
             consumed — the race a 2-slot scheme needs a credit handshake
             for simply cannot occur.  Cost: an (n-1)-chunk comm buffer,
             the same order as the input itself.
  overlap    two staging slots double-buffer the outgoing side: rank d's
             send for step s+1 is staged while step s's DMA drains, and
             the *incoming* DMA for step s+1 (the left neighbor's send)
             streams into slot s+1 while d is still accumulating slot s.
             In the pipelined schedule (compiled kernels) the per-hop
             waits are split: `wait_recv` right before the accumulate
             needs the data, `wait_send` right before a staging slot is
             reused — so DMA and VPU work genuinely overlap.
  codec      the fused variants run dequantize -> fp32 accumulate ->
             requantize *inside* the kernel body on the VMEM-resident
             block: one kernel per ring step instead of three XLA ops
             around an all_to_all (the EQuARX placement, done in Pallas).
             Wire payload per hop is int8/fp8 codes + per-block f32
             scales — the same bytes as compression/collectives.py moves.

Sync discipline: `pipelined=False` (the interpreter path) issues
start();wait() per hop — semantically identical, trivially race-free, and
what the tier-1 CPU suite executes.  `pipelined=True` (compiled TPU) keeps
the Python-unrolled descriptor list and defers waits as described above.
The ring-step loop is a static Python loop (n is a mesh constant), so
every semaphore/slot index is static and both schedules trace to
straight-line Mosaic code.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from ..compression.config import FP8_E4M3_MAX, INT8_MAX, CompressionConfig

#: fp8 wire dtype (None on ml_dtypes builds without it — callers gate)
FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)


def _rdma(src, dst, send_sem, recv_sem, device_id):
    return pltpu.make_async_remote_copy(
        src_ref=src, dst_ref=dst, send_sem=send_sem, recv_sem=recv_sem,
        device_id=device_id, device_id_type=pltpu.DeviceIdType.LOGICAL,
    )


def _chunk_index(my_id, s: int, n: int):
    """Chunk rank d sends at RS step s: (d - s - 1) mod n."""
    return lax.rem(my_id - (s + 1) + 2 * n, n)


# --- plain ring kernels ----------------------------------------------------------------


def make_rs_kernel(n: int, axis_name: str, pipelined: bool):
    """Ring reduce-scatter body.

    Refs: x (n, rows, 128) per rank (row j = this rank's contribution to
    chunk j), o (rows, 128) = the completed chunk this rank owns (index ==
    its own rank), comm (n+1, rows, 128) scratch — slots [0, n-1) receive
    one hop each, slots n-1 and n are the two outgoing staging slots.
    """
    steps = n - 1
    stage0 = steps  # staging slots live past the per-hop recv slots

    def kernel(x_ref, o_ref, comm_ref, send_sems, recv_sems):
        my_id = lax.axis_index(axis_name)
        right = lax.rem(my_id + 1, n)
        dmas = []
        for s in range(steps):
            stage = stage0 + (s % 2)
            if pipelined and s >= 2:
                dmas[s - 2].wait_send()  # staging slot s%2 free again
            if s == 0:
                payload = x_ref[_chunk_index(my_id, 0, n)]
            else:
                if pipelined:
                    dmas[s - 1].wait_recv()  # partial for this chunk arrived
                payload = x_ref[_chunk_index(my_id, s, n)] + comm_ref[s - 1]
            comm_ref[stage] = payload
            d = _rdma(comm_ref.at[stage], comm_ref.at[s],
                      send_sems.at[s], recv_sems.at[s], right)
            d.start()
            if not pipelined:
                d.wait()
            dmas.append(d)
        if pipelined:
            dmas[steps - 1].wait_recv()
        o_ref[...] = x_ref[my_id] + comm_ref[steps - 1]
        if pipelined:
            # drain sends not already absorbed by staging-slot reuse
            for s in range(max(steps - 2, 0), steps):
                dmas[s].wait_send()

    return kernel


def make_ag_kernel(n: int, axis_name: str, pipelined: bool):
    """Ring all-gather body.

    Refs: x (rows, 128) = this rank's chunk, o (n, rows, 128) = every
    rank's chunk.  Hop s forwards chunk (d - s) mod n — its own chunk
    first, then whatever just arrived — straight out of the output buffer
    (each slot is written exactly once per rank, so forwarding in place is
    race-free).
    """
    steps = n - 1

    def kernel(x_ref, o_ref, send_sems, recv_sems):
        my_id = lax.axis_index(axis_name)
        right = lax.rem(my_id + 1, n)
        o_ref[my_id] = x_ref[...]
        dmas = []
        for s in range(steps):
            c = lax.rem(my_id - s + 2 * n, n)
            if pipelined and s >= 1:
                dmas[s - 1].wait_recv()  # the chunk being forwarded arrived
            d = _rdma(o_ref.at[c], o_ref.at[c],
                      send_sems.at[s], recv_sems.at[s], right)
            d.start()
            if not pipelined:
                d.wait()
            dmas.append(d)
        if pipelined:
            dmas[steps - 1].wait_recv()
            for d in dmas:
                d.wait_send()

    return kernel


# --- fused-codec ring kernels ----------------------------------------------------------


def _quantize_block(v, cfg: CompressionConfig):
    """(nblocks, block) f32 -> (codes, (nblocks, 1) f32 scales), matching
    compression/quant.py's deterministic rounding exactly."""
    absmax = jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    if cfg.scheme == "int8":
        scale = jnp.where(absmax > 0, absmax / INT8_MAX, 1.0)
        codes = jnp.clip(jnp.round(v / scale), -INT8_MAX, INT8_MAX)
        return codes.astype(jnp.int8), scale.astype(jnp.float32)
    if cfg.scheme == "fp8":
        scale = jnp.where(absmax > 0, absmax / FP8_E4M3_MAX, 1.0)
        codes = jnp.clip(v / scale, -FP8_E4M3_MAX, FP8_E4M3_MAX)
        return codes.astype(FP8_DTYPE), scale.astype(jnp.float32)
    raise ValueError(f"scheme {cfg.scheme!r} has no fused ring codec")


def _dequantize_block(codes, scale):
    return codes.astype(jnp.float32) * scale


def wire_dtype(cfg: CompressionConfig):
    if cfg.scheme == "int8":
        return jnp.int8
    if cfg.scheme == "fp8":
        if FP8_DTYPE is None:  # pragma: no cover - old ml_dtypes build
            raise NotImplementedError("this JAX build has no float8_e4m3fn")
        return FP8_DTYPE
    raise ValueError(f"scheme {cfg.scheme!r} has no fused ring codec")


def make_fused_rs_kernel(n: int, axis_name: str, cfg: CompressionConfig,
                         pipelined: bool):
    """Fused-codec ring reduce-scatter body.

    Same hop schedule as make_rs_kernel, but each hop's wire payload is
    (codes, scales) and the codec runs on the resident VMEM block:

        recv codes -> dequantize -> + own chunk (fp32) -> requantize -> send

    Refs: x (n, nblocks, block) f32, o (nblocks, block) f32 (the completed
    fp32 chunk — the AG leg requantizes it ONCE, like the XLA schedule),
    code (n+1, nblocks, block) wire-dtype scratch, scale (n+1, nblocks, 1)
    f32 scratch; per-step semaphore arrays for each of the two DMAs.

    Error note: the traveling partial sum is requantized at every hop, so
    the RS-leg error bound is sum over hops of (partial absmax)/(2*codemax)
    — O(n) like the XLA all_to_all path's sum-over-peers bound, but not
    identical; parity tests assert a computed tolerance, not bit equality.
    """
    steps = n - 1
    stage0 = steps

    def kernel(x_ref, o_ref, code_ref, scale_ref,
               csend, crecv, ssend, srecv):
        my_id = lax.axis_index(axis_name)
        right = lax.rem(my_id + 1, n)
        dmas = []
        for s in range(steps):
            stage = stage0 + (s % 2)
            if pipelined and s >= 2:
                for d in dmas[s - 2]:
                    d.wait_send()
            if s == 0:
                payload = x_ref[_chunk_index(my_id, 0, n)]
            else:
                if pipelined:
                    for d in dmas[s - 1]:
                        d.wait_recv()
                payload = x_ref[_chunk_index(my_id, s, n)] + _dequantize_block(
                    code_ref[s - 1], scale_ref[s - 1])
            codes, scales = _quantize_block(payload, cfg)
            code_ref[stage] = codes
            scale_ref[stage] = scales
            pair = (
                _rdma(code_ref.at[stage], code_ref.at[s],
                      csend.at[s], crecv.at[s], right),
                _rdma(scale_ref.at[stage], scale_ref.at[s],
                      ssend.at[s], srecv.at[s], right),
            )
            for d in pair:
                d.start()
            if not pipelined:
                for d in pair:
                    d.wait()
            dmas.append(pair)
        if pipelined:
            for d in dmas[steps - 1]:
                d.wait_recv()
        o_ref[...] = x_ref[my_id] + _dequantize_block(
            code_ref[steps - 1], scale_ref[steps - 1])
        if pipelined:
            for s in range(max(steps - 2, 0), steps):
                for d in dmas[s]:
                    d.wait_send()

    return kernel


def make_fused_ag_kernel(n: int, axis_name: str, cfg: CompressionConfig,
                         pipelined: bool):
    """Fused-codec ring all-gather body.

    The reduced fp32 chunk is quantized ONCE (slot my_id), the ring
    forwards codes+scales verbatim (no requantization — one AG-leg
    quantization, exactly like the XLA schedule's requantize-then-gather),
    and every slot is dequantized to fp32 at the end.

    Refs: x (nblocks, block) f32, o (n, nblocks, block) f32,
    code (n, nblocks, block) wire-dtype, scale (n, nblocks, 1) f32.
    """
    steps = n - 1

    def kernel(x_ref, o_ref, code_ref, scale_ref,
               csend, crecv, ssend, srecv):
        my_id = lax.axis_index(axis_name)
        right = lax.rem(my_id + 1, n)
        codes, scales = _quantize_block(x_ref[...], cfg)
        code_ref[my_id] = codes
        scale_ref[my_id] = scales
        dmas = []
        for s in range(steps):
            c = lax.rem(my_id - s + 2 * n, n)
            if pipelined and s >= 1:
                for d in dmas[s - 1]:
                    d.wait_recv()
            pair = (
                _rdma(code_ref.at[c], code_ref.at[c],
                      csend.at[s], crecv.at[s], right),
                _rdma(scale_ref.at[c], scale_ref.at[c],
                      ssend.at[s], srecv.at[s], right),
            )
            for d in pair:
                d.start()
            if not pipelined:
                for d in pair:
                    d.wait()
            dmas.append(pair)
        if pipelined:
            for d in dmas[steps - 1]:
                d.wait_recv()
        for i in range(n):
            o_ref[i] = _dequantize_block(code_ref[i], scale_ref[i])
        if pipelined:
            for pair in dmas:
                for d in pair:
                    d.wait_send()

    return kernel


def scratch_bytes(n: int, chunk_elems: int,
                  cfg: Optional[CompressionConfig] = None) -> int:
    """Comm+staging scratch footprint of one RS+AG kernel pair — the
    number the wrapper checks against the VMEM budget before choosing the
    Pallas path (falling back to XLA when a payload doesn't fit)."""
    if cfg is None or cfg.scheme in ("none", "bf16"):
        itemsize = 4 if cfg is None else (2 if cfg.scheme == "bf16" else 4)
        return (n + 1) * chunk_elems * itemsize
    nblocks = chunk_elems // cfg.block
    code = (n + 1) * chunk_elems * 1
    scales = (n + 1) * nblocks * 4
    return code + scales


# --- fused computation-collective kernels ----------------------------------------------
#
# The arXiv 2305.06942 placement done on this file's DMA machinery: the
# collective's per-hop transfer and the matmul that produces/consumes it
# interleave inside ONE kernel, so the MXU works on hop h's block while
# hop h+1's remote DMA is in flight and the gathered/partial tensor never
# materializes as a separate XLA op.


def _mxu_dot(a, b, block_m: int = 0, block_n: int = 0):
    """fp32-accumulated a @ b, optionally split into (block_m, block_n)
    MXU tiles (static Python loops — straight-line Mosaic).  0 = whole
    operand in one pass.  The tiles share the VMEM budget with the
    flash tiles and ring comm slots."""
    m, _ = a.shape
    nn = b.shape[1]
    bm = block_m or m
    bn = block_n or nn
    if bm >= m and bn >= nn:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    rows = []
    for i in range(0, m, bm):
        cols = [
            jnp.dot(a[i:i + bm], b[:, j:j + bn],
                    preferred_element_type=jnp.float32)
            for j in range(0, nn, bn)
        ]
        rows.append(cols[0] if len(cols) == 1 else jnp.concatenate(cols, 1))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, 0)


def make_ag_matmul_kernel(n: int, axis_name: str, pipelined: bool,
                          block_m: int = 0, block_n: int = 0):
    """All-gather-matmul body: y = x @ concat_rows(W_0..W_{n-1}) with the
    W shards rotating around the ring, never gathered into one buffer.

    Refs: x (n, M, Ks) — the local activation pre-blocked by contraction
    chunk (block c multiplies shard W_c); w (Ks, N) — this rank's weight
    shard; o (M, N) fp32 accumulator/output; comm (n, Ks, N) scratch —
    slot c holds W_c once it arrives (own slot seeded before hop 0, every
    other slot written by exactly one incoming DMA, so forwarding in
    place is race-free — the make_ag_kernel argument).

    Hop s forwards the shard that arrived at hop s-1 (own shard at s=0)
    and the MXU consumes that same shard while the DMA drains: compute
    for hop s overlaps communication for hop s+1's payload.
    """
    steps = n - 1

    def kernel(x_ref, w_ref, o_ref, comm_ref, send_sems, recv_sems):
        my_id = lax.axis_index(axis_name)
        right = lax.rem(my_id + 1, n)
        comm_ref[my_id] = w_ref[...]
        dmas = []
        acc = None
        for s in range(steps):
            c = lax.rem(my_id - s + 2 * n, n)
            if pipelined and s >= 1:
                dmas[s - 1].wait_recv()  # the shard being forwarded arrived
            d = _rdma(comm_ref.at[c], comm_ref.at[c],
                      send_sems.at[s], recv_sems.at[s], right)
            d.start()
            if not pipelined:
                d.wait()
            # MXU consumes shard c while hop s's DMA is in flight
            part = _mxu_dot(x_ref[c], comm_ref[c], block_m, block_n)
            acc = part if acc is None else acc + part
            dmas.append(d)
        if pipelined and steps:
            dmas[steps - 1].wait_recv()
        c_last = lax.rem(my_id - steps + 2 * n, n)
        part = _mxu_dot(x_ref[c_last], comm_ref[c_last], block_m, block_n)
        o_ref[...] = part if acc is None else acc + part
        if pipelined:
            for d in dmas:
                d.wait_send()

    return kernel


def make_matmul_rs_kernel(n: int, axis_name: str, pipelined: bool,
                          block_m: int = 0, block_n: int = 0):
    """Matmul-reduce-scatter body: each rank's partial product
    x_local @ W_local reduce-scatters around the ring, with each row
    chunk's matmul computed right before it is staged into the outbound
    slot — the backward-epilogue fusion (partials never materialize as a
    separate [M, N] tensor).

    Refs: x (n, Mc, K) — local activation pre-blocked by output row
    chunk; w (K, N) — local weight; o (Mc, N) fp32 — the completed
    summed chunk this rank owns (index == its rank, matching
    lax.psum_scatter(scatter_dimension=0)); comm (n+1, Mc, N) fp32
    scratch — per-hop recv slots + two outbound staging slots (the
    make_rs_kernel layout; partials travel fp32).

    Hop s's matmul (chunk (d-s-1) mod n) runs before hop s-1's recv is
    awaited, so the MXU fills the DMA's drain time.
    """
    steps = n - 1
    stage0 = steps

    def kernel(x_ref, w_ref, o_ref, comm_ref, send_sems, recv_sems):
        my_id = lax.axis_index(axis_name)
        right = lax.rem(my_id + 1, n)
        dmas = []
        for s in range(steps):
            stage = stage0 + (s % 2)
            if pipelined and s >= 2:
                dmas[s - 2].wait_send()  # staging slot s%2 free again
            c = _chunk_index(my_id, s, n)
            # MXU work for this hop, issued while hop s-1's DMA drains
            part = _mxu_dot(x_ref[c], w_ref[...], block_m, block_n)
            if s == 0:
                payload = part
            else:
                if pipelined:
                    dmas[s - 1].wait_recv()
                payload = part + comm_ref[s - 1]
            comm_ref[stage] = payload
            d = _rdma(comm_ref.at[stage], comm_ref.at[s],
                      send_sems.at[s], recv_sems.at[s], right)
            d.start()
            if not pipelined:
                d.wait()
            dmas.append(d)
        # own chunk's matmul overlaps the final hop's DMA
        own = _mxu_dot(x_ref[my_id], w_ref[...], block_m, block_n)
        if pipelined and steps:
            dmas[steps - 1].wait_recv()
        o_ref[...] = own + comm_ref[steps - 1] if steps else own
        if pipelined:
            for s in range(max(steps - 2, 0), steps):
                dmas[s].wait_send()

    return kernel


def make_shift_kernel(n: int, axis_name: str, shift: int = 1):
    """Single-hop ring rotation — `lax.ppermute(x, axis, [(i, (i+shift) %
    n)])` as one remote DMA on the data plane.  The building block ring
    attention's blockwise KV rotation rides (parallel/ring_attention.py):
    one RDMA per hop instead of a collective-permute, same bytes.

    Refs: x (rows, LANES) payload, o (rows, LANES) the rotated result.
    One hop has nothing to pipeline: start(); wait() on both schedules.
    """

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        my_id = lax.axis_index(axis_name)
        dst = lax.rem(my_id + shift + 2 * n, n)
        d = _rdma(x_ref, o_ref, send_sem, recv_sem, dst)
        d.start()
        d.wait()

    return kernel


def ag_matmul_scratch_bytes(n: int, ks: int, nn: int, m: int,
                            itemsize: int) -> int:
    """VMEM scratch of one all-gather-matmul call: the n rotating weight
    slots plus the fp32 accumulator — checked against the same
    KFT_PALLAS_VMEM_MIB budget the ring collectives and flash tiles
    share."""
    return n * ks * nn * itemsize + m * nn * 4


def matmul_rs_scratch_bytes(n: int, mc: int, nn: int) -> int:
    """VMEM scratch of one matmul-reduce-scatter call: (n-1) per-hop
    fp32 recv slots + two staging slots + the fp32 output chunk."""
    return (n + 2) * mc * nn * 4

"""Pipelined collective execution (paper §4.3.2, Fig. 9).

Sequentially executing Algorithm 1's phases leaves the DCN idle while
the ICI phases run (and vice versa).  The schedule IR's ``ChunkLoop``
models the full 3-phase software pipeline with a 1-stage skew —

    iter i:  RS_ici(chunk i)   |   AR_dcn(chunk i-1)   |   AG_ici(chunk i-2)

— and ``core/cost_model.py`` / ``core/transport_sim.py`` price and
simulate all of its stages against the real fabric's α–β constants.

The *executable* emulation below pipelines only where the emulated
backend can actually benefit: the slow C2C hop plus the wire codec.
The ICI ReduceScatter/AllGather run un-chunked on the whole payload —
XLA's CPU runtime executes the per-device program in order, so a
k-way split of an ICI collective buys no overlap and measurably costs
~2x the unsplit collective at identical total bytes (one extra
payload-sized materialisation per split).  The pod hop, by contrast,
is chunked into ``n_chunks`` pieces of the post-RS shard and
double-buffered.

The pipeline fill and drain are *peeled* out of the ``lax.scan``: the
loop body only runs steady-state iterations, so no collective ever
fires on a zero-filled carry — exactly k pod reductions are executed
for k chunks (the old in-loop fill cost k+2, two of them on zeros,
plus the codec work when compression was on).

When a wire codec rides the C2C hop, the pod reduction is split into an
``encode`` stage (amax → shared scale → quantize; cheap nb-sized pmax)
and a ``transfer`` stage (the int8 ring + decode).  The scan carry
holds the *pre-quantized* next chunk, so iteration i traces
compress(i) next to C2C(i-1) with no data dependency between them —
the double-buffering that lets XLA hide the codec passes behind the
DCN transfer (priced as the ``codec_s`` pipeline stage by
``core/cost_model.py``).

The mechanism-faithful ring variant (``use_ring=True``) replaces the
pod-axis all-reduce with the explicit c2cRed P2P ring of
``primitives.c2c_red_ring`` — chunk scheduling identical to the paper's
border-rank pipeline of Fig. 5/9.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import primitives, scopes
from . import schedule as schedule_ir


def execute_chunk_loop(step: "schedule_ir.ChunkLoop", flat: jax.Array,
                       cfg, weight: jax.Array | None = None) -> jax.Array:
    """ChunkLoop interpreter of the schedule IR (DESIGN.md §9): run the
    loop body's start/c2c/end phases chunk-pipelined.  The shipped
    pipelined schedules all carry the AllReduceH body (ReduceScatter →
    c2cRed → AllGather) — the scan below *is* that body's pipeline; a
    builder emitting a different chunked body must extend this.
    ``weight`` is the deferred cluster-scale (schedule ``Scale`` step),
    applied at the C2C stage on shard-sized data (or folded into the
    codec) instead of a full-payload pass."""
    kinds = {type(s) for s in step.body}
    if not {schedule_ir.IntraReduceScatter, schedule_ir.C2CRed,
            schedule_ir.IntraAllGather} <= kinds:
        raise NotImplementedError(
            f"chunk-pipelined execution only implements the AllReduceH "
            f"body; got {sorted(k.__name__ for k in kinds)}")
    if any(isinstance(s, schedule_ir.C2CRed) and s.scatter for s in step.body):
        raise NotImplementedError(
            "the border-communicator exchange is not chunk-pipelined")
    return pipelined_hier_psum(flat, cfg, weight=weight)


def _codec_stages(cfg, flat, shard_n: int, use_ring: bool,
                  weight: jax.Array | None):
    """(encode, transfer) pair with transfer(encode(s)) equal to the
    sequential pod reduction of shard ``s``.  The split is what the
    double-buffered scan carries across iterations: ``encode`` is the
    local compress stage (plus the nb-sized shared-scale pmax for int8),
    ``transfer`` moves the encoded payload over the DCN and decodes."""
    pod = cfg.pod_axis
    if use_ring:
        def encode(shard):
            if weight is not None:
                return shard * weight.astype(shard.dtype)
            return shard

        def transfer(enc):
            return primitives.c2c_red_ring(enc, pod)
        return encode, transfer
    if cfg.compression == "int8":
        from . import compression

        def encode(shard):
            return compression.int8_encode(shard, pod, weight=weight)

        def transfer(enc):
            q, scale = enc
            return compression.int8_transfer(q, scale, pod, shard_n,
                                             flat.dtype)
        return encode, transfer
    if cfg.compression == "bf16":
        def encode(shard):
            if weight is not None:
                shard = shard * weight.astype(shard.dtype)
            return shard.astype(jnp.bfloat16)

        def transfer(enc):
            return lax.psum(enc, pod).astype(flat.dtype)
        return encode, transfer
    if cfg.compression is not None:
        from . import compression

        def encode(shard):
            return shard

        def transfer(enc):
            return compression.compressed_psum(enc, pod, cfg.compression,
                                               weight=weight)
        return encode, transfer

    def encode(shard):
        if weight is not None:
            return shard * weight.astype(shard.dtype)
        return shard

    def transfer(enc):
        return primitives.c2c_red(enc, pod)
    return encode, transfer


def pipelined_hier_psum(flat: jax.Array, cfg, use_ring: bool = False,
                        weight: jax.Array | None = None) -> jax.Array:
    """AllReduceH on a 1-D array, chunked + phase-pipelined.

    flat must already be padded to a multiple of intra_size; returns the
    all-reduced array of the same shape.  Buffers from the packed data
    path (``core/packing.py``) are pre-aligned to ``intra·k``, so the
    chunk split below never re-pads (``pad == 0``) — the pad branch
    only serves legacy unpacked callers.
    """
    assert flat.ndim == 1
    intra, pod = cfg.intra_axis, cfg.pod_axis
    if pod is None:
        # No C2C phase to pipeline against: the chunk loop would only
        # add k-1 extra α costs and a scan around what is exactly one
        # intra-cluster all-reduce.  Fall back to the plain native psum
        # (== ReduceScatter+AllGather fused by the platform library).
        if weight is not None:
            flat = flat * weight.astype(flat.dtype)
        return lax.psum(flat, intra)
    isize = primitives.axis_size(intra)
    k = max(1, int(cfg.n_chunks))
    n = flat.size
    # the SHARD (post-ReduceScatter, 1/intra of the payload) is what the
    # chunk loop iterates over, so the flat buffer must split into
    # k·isize equal tiles; packed buffers are pre-aligned to this
    pad = (-n) % (k * isize)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard_n = flat.size // isize
    chunk = shard_n // k
    encode, transfer = _codec_stages(cfg, flat, chunk, use_ring, weight)
    # chaos seam: encoded chunks pass through the injection hook on their
    # way onto the DCN — for int8 the hook sees the (q, scale) pair, so
    # bit-flips land in real int8 blocks (identity when no hook installed)
    _raw_encode, _raw_transfer = encode, transfer

    # each chunk's phases carry the IR names, under the ChunkLoop scope
    # the executor opens
    def encode(shard):
        with scopes.scoped(schedule_ir.C2CRed):
            return _raw_encode(shard)

    def transfer(enc):
        with scopes.scoped(schedule_ir.C2CRed):
            return _raw_transfer(primitives.apply_inject(enc, "chunk_c2c"))
    # One intra ReduceScatter / AllGather on the whole payload: on the
    # emulated backend splitting the ICI phases k-ways buys no overlap
    # (XLA executes the per-device program in order) and pays an extra
    # payload-sized materialisation per split — the measured cost of a
    # k-chunked RS/AG is ~2x the unsplit one at identical bytes.  The
    # chunk pipeline therefore lives where it pays: on the C2C hop and
    # the codec (below).  The real-fabric 3-phase overlap is still
    # modeled by the ChunkLoop schedule IR (core/cost_model.py prices
    # all four stages; core/transport_sim.py simulates them).
    with scopes.scoped(schedule_ir.IntraReduceScatter):
        rs = primitives.hom_reduce_scatter(flat, intra)
    if k == 1:
        red = transfer(encode(rs))
        with scopes.scoped(schedule_ir.IntraAllGather):
            out = primitives.hom_all_gather(red, intra)
        return out[:n]
    chunks = rs.reshape(k, chunk)

    def write(out, ar, i):
        # chunk i's reduced result lands at its shard offset via an
        # in-place dynamic_update_slice on the carried buffer (XLA
        # aliases it across iterations) — no concatenate.
        return lax.dynamic_update_slice(out, ar, (i * chunk,))

    # --- double-buffered C2C loop: compress(i) overlaps transfer(i-1).
    # The peel keeps every collective off zero carries: exactly k pod
    # reductions run for k chunks (the old in-loop fill cost k+2, two
    # of them on zeros, plus the codec work when compression was on).
    enc0 = encode(chunks[0])

    def step(carry, i):
        enc_prev, out = carry
        xi = lax.dynamic_index_in_dim(chunks, i, 0, keepdims=False)
        # independent stages; XLA may run them concurrently
        enc_i = encode(xi)                                  # compress(i)
        ar_i = transfer(enc_prev)                           # DCN C2C(i-1)
        return (enc_i, write(out, ar_i, i - 1)), None

    out0 = jnp.zeros((shard_n,), flat.dtype)
    (enc_last, red), _ = lax.scan(step, (enc0, out0), jnp.arange(1, k))
    red = write(red, transfer(enc_last), k - 1)   # drain: C2C of chunk k-1
    with scopes.scoped(schedule_ir.IntraAllGather):
        out = primitives.hom_all_gather(red, intra)
    return out[:n]


def pipelined_all_gather(x: jax.Array, cfg) -> jax.Array:
    """AllGatherH with the pod ring chunked so the intra Bcast of pod
    shard j overlaps the DCN hop of pod shard j+1 (Fig. 9's AllGather
    example).  Returns values stacked on a new leading (pods*intra) dim
    ordering pods-major."""
    assert x.ndim >= 1
    pod, intra = cfg.pod_axis, cfg.intra_axis
    if pod is None:
        return primitives.hom_all_gather(x, intra)
    n = primitives.axis_size(pod)
    my = lax.axis_index(pod)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(cur, _):
        nxt = lax.ppermute(cur, pod, perm)            # DCN hop (chunk j+1)
        bcast = primitives.hom_all_gather(cur, intra)  # ICI Bcast (chunk j)
        return nxt, bcast

    _, gathered = lax.scan(step, x, None, length=n)    # (P, intra*x0, ...)
    # slot j holds pod (my - j) % n; realign to absolute order.
    out = gathered[(my - jnp.arange(n)) % n]
    return out.reshape((n * gathered.shape[1],) + x.shape[1:])

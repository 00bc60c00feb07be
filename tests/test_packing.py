"""Packed data path unit + property tests (core/packing.py).

Layout invariants (offset disjointness, padding alignment, wire-byte
counts per dtype), pack/unpack roundtrip identity over mixed
dtypes/shapes/pytree structures, the int8 block-codec edge cases at
sizes not a multiple of the block, and jnp-vs-Pallas codec equivalence.
The multi-device zero-copy (jaxpr) assertions live in
tests/mdscripts/check_packed.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.core import collectives, compression, packing
from repro.core.collectives import CommConfig
from repro.kernels import quant as quant_kernels

RNG = np.random.default_rng(7)


def test_block_constant_matches_kernel():
    """The stdlib layout core duplicates kernels.quant.BLOCK so the
    no-jax CI gate can import it — the two must agree."""
    assert packing.DEFAULT_BLOCK == quant_kernels.BLOCK == compression.BLOCK


# ---------------------------------------------------------------------------
# Layout properties
# ---------------------------------------------------------------------------

_DTYPES = ("float32", "bfloat16", "float16")


@hypothesis.given(n_leaves=st.integers(1, 12),
                  world=st.sampled_from((1, 2, 4, 8)),
                  n_chunks=st.sampled_from((1, 2, 4)),
                  block=st.sampled_from((1, 1024)),
                  seed=st.integers(0, 10 ** 6))
@hypothesis.settings(max_examples=40, deadline=None)
def test_layout_invariants(n_leaves, world, n_chunks, block, seed):
    rng = np.random.default_rng(seed)
    metas = []
    for _ in range(n_leaves):
        dt = _DTYPES[rng.integers(len(_DTYPES))]
        shape = tuple(int(s) for s in rng.integers(1, 9,
                                                   size=rng.integers(1, 4)))
        size = int(np.prod(shape))
        metas.append((dt, shape, size))
    lay = packing.plan_layout(metas, world=world, n_chunks=n_chunks,
                              block=block)
    lay.validate()       # disjointness / bounds / tight packing
    align = packing.comm_alignment(world, n_chunks, block)
    for seg in lay.segments:
        # padding baked in once: every downstream alignment holds
        assert seg.padded % align == 0
        assert seg.padded % world == 0                      # intra shard
        assert seg.padded % (world * n_chunks) == 0          # chunk split
        shard_per_chunk = seg.padded // (world * n_chunks)
        assert shard_per_chunk % block == 0                  # int8 blocks
        assert seg.used <= seg.padded < seg.used + align
        # wire bytes follow the segment's own dtype (no fp32 upcast)
        assert seg.wire_bytes == seg.padded * packing.itemsize_of(seg.dtype)
    # every leaf covered exactly once, grouped by dtype
    assert sum(sl.size for sl in lay.slots) == sum(m[2] for m in metas)
    assert lay.used_total == sum(m[2] for m in metas)
    # segment bounds tile the concatenated master view contiguously
    bounds = lay.segment_bounds()
    assert bounds[0][1] == 0
    for (_, s0, e0), (_, s1, _) in zip(bounds, bounds[1:]):
        assert e0 == s1
    assert bounds[-1][2] == lay.padded_total


@hypothesis.given(n_leaves=st.integers(1, 10), seed=st.integers(0, 10 ** 6))
@hypothesis.settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip_mixed_dtypes(n_leaves, seed):
    rng = np.random.default_rng(seed)
    leaves = []
    for _ in range(n_leaves):
        dt = _DTYPES[rng.integers(len(_DTYPES))]
        shape = tuple(int(s) for s in rng.integers(1, 7,
                                                   size=rng.integers(1, 3)))
        leaves.append(jnp.asarray(rng.normal(size=shape), dt))
    lay = packing.plan_layout(packing.tree_metas(leaves), world=4,
                              n_chunks=2, block=1)
    bufs = packing.pack(lay, leaves)
    back = packing.unpack(lay, bufs)
    assert len(back) == len(leaves)
    for a, b in zip(leaves, back):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # padding is zero-filled (collectives sum it away harmlessly)
    for seg in lay.segments:
        tail = np.asarray(bufs[seg.dtype][seg.used:], np.float32)
        assert np.all(tail == 0.0)


def test_pack_roundtrip_pytree_structures():
    tree = {"a": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                  "b": jnp.ones((5,), jnp.bfloat16)},
            "c": [jnp.zeros((2, 2, 2), jnp.float32),
                  jnp.full((3,), 2.0, jnp.float16)]}
    leaves, treedef = jax.tree.flatten(tree)
    lay = packing.plan_layout(packing.tree_metas(leaves), world=8,
                              n_chunks=4, block=1024)
    back = jax.tree.unflatten(treedef, packing.unpack(
        lay, packing.pack(lay, leaves)))
    for a, b in zip(leaves, jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.all(a == b))


def test_wire_bytes_per_dtype_regression():
    """Satellite acceptance: bf16 leaves cost 2 bytes/elem on the wire
    — the old tree_flatten_f32 silently doubled them to 4.  Goes
    through the collectives-layer entry (``comm_layout``) with an
    explicit world so it runs outside shard_map."""
    leaves = [jnp.zeros((1000,), jnp.float32),
              jnp.zeros((2000,), jnp.bfloat16)]
    lay = collectives.comm_layout(
        leaves, CommConfig(mode="hier", n_chunks=1, compression=None),
        world=4)
    # the int8 codec requests BLOCK-aligned segments via the same entry
    lay8 = collectives.comm_layout(
        leaves, CommConfig(mode="hier", n_chunks=2, compression="int8"),
        world=4)
    for seg in lay8.segments:
        assert seg.padded % (4 * 2 * packing.DEFAULT_BLOCK) == 0
    wb = lay.wire_bytes()
    assert wb["float32"] == 4 * lay.segment("float32").padded
    assert wb["bfloat16"] == 2 * lay.segment("bfloat16").padded
    # the bf16 segment's padded extent is elementwise-tight (pad < align)
    assert lay.segment("bfloat16").padded < 2000 + 4
    # fp32-upcasting everything would have doubled the bf16 bytes:
    upcast_bytes = 4 * (lay.segment("bfloat16").padded)
    assert wb["bfloat16"] * 2 == upcast_bytes


def test_bucket_layout_bounds_and_gaps():
    buckets = [[("float32", (10,), 10), ("float32", (3,), 3)],
               [("float32", (7,), 7)],
               [("float32", (1,), 1)]]
    lay = packing.plan_bucket_layout(buckets, align=[8, 4, 2])
    lay.validate()
    assert len(lay.bucket_bounds) == 3
    prev_end = 0
    for (s, e), a in zip(lay.bucket_bounds, (8, 4, 2)):
        assert s == prev_end           # contiguous slices of one buffer
        assert (e - s) % a == 0        # per-bucket schedule alignment
        prev_end = e
    assert lay.segments[0].padded == prev_end
    # pack_bucketed zero-fills inter-bucket gaps (scatter writes into a
    # zeros-initialised buffer — no concatenate is traced)
    pieces = [jnp.arange(10.0), jnp.arange(3.0), jnp.arange(7.0),
              jnp.arange(1.0)]
    buf = packing.pack_bucketed(lay, pieces)
    assert buf.shape == (prev_end,)
    np.testing.assert_array_equal(np.asarray(buf[13:16]), 0.0)


def test_plan_bucket_layout_rejects_mismatched_aligns():
    with pytest.raises(ValueError, match="one alignment per bucket"):
        packing.plan_bucket_layout([[("float32", (4,), 4)]], align=[1, 2])


def test_unknown_wire_dtype_raises():
    with pytest.raises(ValueError, match="unknown wire dtype"):
        packing.itemsize_of("complex64")


# ---------------------------------------------------------------------------
# int8 block codec: edge cases + Pallas/jnp equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 3, 1000, 1023, 1024, 1025, 3000, 4096])
def test_quant_roundtrip_edge_sizes(n):
    """Sizes not a multiple of the block exercise the legacy pad branch
    (the packed path never hits it); the roundtrip error stays within
    the per-block quantization bound either way."""
    x = jnp.asarray(RNG.normal(size=(n,)) * 3.0, jnp.float32)
    q, s = compression.quantize_int8(x)
    y = compression.dequantize_int8(q, s, n)
    assert y.shape == (n,)
    bound = float(jnp.max(jnp.abs(x))) / 127.0 * 0.51 + 1e-6
    assert float(jnp.max(jnp.abs(y - x))) <= bound * 1.05


def test_dequant_gain_epilogue():
    """The fused epilogue: gain multiplies the nb-sized scale vector,
    equivalent to scaling the decoded payload."""
    x = jnp.asarray(RNG.normal(size=(2048,)), jnp.float32)
    q, s = compression.quantize_int8(x)
    plain = compression.dequantize_int8(q, s, 2048)
    gained = compression.dequantize_int8(q, s, 2048, gain=0.25)
    np.testing.assert_allclose(np.asarray(gained), np.asarray(plain) * 0.25,
                               rtol=1e-6, atol=1e-7)


def test_pallas_codec_matches_jnp(monkeypatch):
    """REPRO_PALLAS_QUANT=1 routes the codec through the fused Pallas
    kernels (interpret mode on CPU) — bit-identical quantization to the
    jnp mirror."""
    x = jnp.asarray(RNG.normal(size=(4096,)) * 2.0, jnp.float32)
    monkeypatch.setenv("REPRO_PALLAS_QUANT", "0")
    qj, sj = compression.quantize_int8(x)
    amax_j = compression._block_amax(x)
    monkeypatch.setenv("REPRO_PALLAS_QUANT", "1")
    assert compression.use_pallas()
    qp, sp = compression.quantize_int8(x)
    amax_p = compression._block_amax(x)
    np.testing.assert_array_equal(np.asarray(qj), np.asarray(qp))
    np.testing.assert_allclose(np.asarray(sj), np.asarray(sp), rtol=1e-7)
    np.testing.assert_allclose(np.asarray(amax_j), np.asarray(amax_p),
                               rtol=1e-7)
    # scaled-quant + dequant kernels agree with the jnp mirror too
    scale = jnp.maximum(amax_p, 1e-6) / 127.0
    qp2 = compression._encode_scaled(x, scale)
    yp = compression._decode(qp2, scale)
    monkeypatch.setenv("REPRO_PALLAS_QUANT", "0")
    qj2 = compression._encode_scaled(x, scale)
    yj = compression._decode(qj2, scale)
    np.testing.assert_array_equal(np.asarray(qp2), np.asarray(qj2))
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yj), rtol=1e-6)
    # the hot collective decode consumes the ring's int32 partial sums:
    # the Pallas path must accept them and agree with the jnp mirror
    q32 = (qj2.astype(jnp.int32)) * 3
    yj32 = compression._decode(q32, scale)
    monkeypatch.setenv("REPRO_PALLAS_QUANT", "1")
    yp32 = compression._decode(q32, scale)
    np.testing.assert_allclose(np.asarray(yp32), np.asarray(yj32), rtol=1e-6)


def test_zero_amax_never_divides_by_zero(monkeypatch):
    """Shared-scale codec zero-amax guard: an all-zero block must
    encode/decode to finite exact zeros on BOTH backends, even when the
    caller hands the raw (unclamped) zero scale to the scaled quantizer
    — the kernel clamps to 1.0 exactly like ``_quant_kernel``."""
    z = jnp.zeros((2 * quant_kernels.BLOCK,), jnp.float32)
    zero_scale = jnp.zeros((2,), jnp.float32)
    for env in ("0", "1"):
        monkeypatch.setenv("REPRO_PALLAS_QUANT", env)
        q = compression._encode_scaled(z, zero_scale)
        assert np.all(np.asarray(q) == 0), env
        qq, ss = compression.quantize_int8(z)
        y = compression.dequantize_int8(qq, ss, z.size)
        assert np.all(np.isfinite(np.asarray(y))) and np.all(
            np.asarray(y) == 0.0), env
    # the Pallas scaled kernel, addressed directly with scale 0
    qk = quant_kernels.quant_scaled_call(z, zero_scale)
    assert np.all(np.asarray(qk) == 0)


def _random_leaf_set(rng, n_leaves):
    leaves = []
    for _ in range(n_leaves):
        shape = tuple(int(s) for s in rng.integers(1, 40,
                                                   size=rng.integers(1, 3)))
        leaves.append(jnp.asarray(rng.normal(size=shape) * 2.0, jnp.float32))
    return leaves


@hypothesis.given(n_leaves=st.integers(1, 8), seed=st.integers(0, 10 ** 6))
@hypothesis.settings(max_examples=15, deadline=None)
def test_fused_pack_quant_matches_composition(n_leaves, seed):
    """Tentpole conformance: the fused pack+quantize kernel
    (``kernels/quant.py``: slot-map scatter writes + one
    amax+scale+round+clip pass) matches the two-pass composition
    scatter-pack -> standalone quantizer: the int8 wire blocks are
    BIT-identical; the f32 scales agree to 1 ulp (separately compiled
    programs may fold the /127 differently)."""
    rng = np.random.default_rng(seed)
    leaves = _random_leaf_set(rng, n_leaves)
    lay = packing.plan_layout(packing.tree_metas(leaves), world=1,
                              block=quant_kernels.BLOCK)
    seg = lay.segments[0]
    pieces = [(sl.offset, lf) for sl, lf in zip(lay.slots, leaves)]
    fq, fs = quant_kernels.fused_pack_quant_call(pieces, seg.padded)
    buf = packing.pack(lay, leaves)[seg.dtype]
    cq, cs = compression.quantize_int8(buf)
    np.testing.assert_array_equal(np.asarray(fq), np.asarray(cq))
    np.testing.assert_allclose(np.asarray(fs), np.asarray(cs), rtol=1e-7)


def test_pack_slots_call_matches_scatter_pack():
    """The Pallas in-place slot writer fills the persistent comm buffer
    identically to the jnp scatter-pack (same offsets, zero tail)."""
    rng = np.random.default_rng(11)
    leaves = _random_leaf_set(rng, 5)
    lay = packing.plan_layout(packing.tree_metas(leaves), world=1,
                              block=quant_kernels.BLOCK)
    seg = lay.segments[0]
    pieces = [(sl.offset, lf) for sl, lf in zip(lay.slots, leaves)]
    got = quant_kernels.pack_slots_call(pieces, seg.padded)
    want = packing.pack(lay, leaves)[seg.dtype]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.all(np.asarray(got[seg.used:]) == 0.0)


def test_comm_alignment_floor():
    """The alignment is a multiple of lcm(world·n_chunks, block) — the
    contract the ISSUE states — and of every derived divisor."""
    import math
    for world, k, block in ((8, 4, 1024), (4, 1, 1024), (2, 2, 1), (1, 1, 1)):
        a = packing.comm_alignment(world, k, block)
        assert a % math.lcm(world * k, block) == 0
        assert a % (world * k) == 0 and a % block == 0


# OLMo-1B's float32 gradient at 8 of its 16 layers, in elements
OLMO_USED = 639_893_504


@pytest.mark.parametrize("world,n_chunks,block", [(4, 1, 1), (2, 1, 1),
                                                  (4, 3, 1024)])
def test_large_segment_padded_to_whole_spans(world, n_chunks, block):
    """A large segment synced across chips keeps its comm alignment and
    is padded on so that every intra shard is whole reduce-scatter
    spans, for less than 1/64 of it."""
    seg = packing.plan_layout([("float32", (OLMO_USED,), OLMO_USED)],
                              world=world, n_chunks=n_chunks,
                              block=block).segments[0]
    assert seg.padded % packing.comm_alignment(world, n_chunks, block) == 0
    for n in (2, world):
        assert (seg.padded // n) % packing.RS_SPAN == 0
    assert 0 < seg.padded - seg.used < seg.used / 64


def test_whole_spans_only_where_synced_and_large():
    # the four-chip cell: 512 rows of 128 more on each of two shards
    assert packing.padded_size(OLMO_USED, 4, world=4) == 640_024_576
    # one chip: the alignment alone, so the one-chip step is unchanged
    assert packing.padded_size(OLMO_USED, 1, world=1) == OLMO_USED
    # under 64 units of world * RS_SPAN: the alignment alone
    unit = 4 * packing.RS_SPAN
    assert packing.padded_size(64 * unit - 4, 4, world=4) == 64 * unit - 4
    assert packing.padded_size(64 * unit + 4, 4, world=4) == 65 * unit


# ---------------------------------------------------------------------------
# Elastic shard remap (remap_shard_ops / apply_remap_ops)
# ---------------------------------------------------------------------------

def _segment_truth(lay, shards, world):
    """Reassemble each dtype segment from per-rank shards — the
    ground-truth inverse of collectives.zero1_local_shard's slicing."""
    segs = {}
    base = 0
    for seg in lay.segments:
        per = seg.padded // world
        segs[seg.dtype] = np.concatenate(
            [np.asarray(s)[base:base + per] for s in shards])
        base += per
    return segs


def _shards_from_segments(lay, segs, world):
    per_rank = lay.padded_total // world
    out = []
    for r in range(world):
        parts = []
        for seg in lay.segments:
            per = seg.padded // world
            parts.append(segs[seg.dtype][r * per:(r + 1) * per])
        out.append(np.concatenate(parts))
        assert out[-1].size == per_rank
    return out


@hypothesis.given(n_leaves=st.integers(1, 8),
                  old_world=st.sampled_from((1, 2, 4, 8)),
                  new_world=st.sampled_from((1, 2, 3, 4, 8)),
                  seed=st.integers(0, 10 ** 6))
@hypothesis.settings(max_examples=40, deadline=None)
def test_remap_preserves_segment_contents(n_leaves, old_world, new_world,
                                          seed):
    """Every payload element keeps its (segment, in-segment offset)
    identity across the remap: reassembling the segments from the NEW
    shards gives back the old segments (up to each side's zero tail)."""
    rng = np.random.default_rng(seed)
    metas = []
    for _ in range(n_leaves):
        dt = _DTYPES[rng.integers(len(_DTYPES))]
        n = int(rng.integers(1, 200))
        metas.append((dt, (n,), n))
    # block=1 keeps padding minimal so odd worlds stay divisible
    old = packing.plan_layout(metas, world=old_world, block=1)
    new = packing.plan_layout(metas, world=new_world, block=1)
    segs = {s.dtype: rng.standard_normal(s.padded).astype(np.float32)
            for s in old.segments}
    # tails beyond `used` are zero in the real master (pack zero-inits)
    for s in old.segments:
        segs[s.dtype][s.used:] = 0.0
    old_shards = _shards_from_segments(old, segs, old_world)
    ops = packing.remap_shard_ops(old, new, old_world=old_world,
                                  new_world=new_world)
    new_shards = packing.apply_remap_ops(
        ops, old_shards, new.padded_total // new_world)
    back = _segment_truth(new, new_shards, new_world)
    for s_old, s_new in zip(old.segments, new.segments):
        n = min(s_old.padded, s_new.padded)
        np.testing.assert_array_equal(back[s_new.dtype][:n],
                                      segs[s_old.dtype][:n])
        assert np.all(back[s_new.dtype][s_new.used:] == 0.0)


def test_remap_identity_world():
    metas = [("float32", (100,), 100), ("bfloat16", (64,), 64)]
    lay = packing.plan_layout(metas, world=4, block=1)
    rng = np.random.default_rng(0)
    shards = [rng.standard_normal(lay.padded_total // 4).astype(np.float32)
              for _ in range(4)]
    ops = packing.remap_shard_ops(lay, lay, old_world=4, new_world=4)
    out = packing.apply_remap_ops(ops, shards, lay.padded_total // 4)
    for a, b in zip(out, shards):
        np.testing.assert_array_equal(a, b)


def test_remap_rejects_different_leaf_contents():
    a = packing.plan_layout([("float32", (100,), 100)], world=2, block=1)
    b = packing.plan_layout([("float32", (101,), 101)], world=2, block=1)
    with pytest.raises(ValueError, match="different leaf contents"):
        packing.remap_shard_ops(a, b, old_world=2, new_world=2)


def test_remap_rejects_indivisible_world():
    lay = packing.plan_layout([("float32", (100,), 100)], world=2, block=1)
    # padded for world=2 is even; world=7 won't divide it
    assert lay.segments[0].padded % 7 != 0
    with pytest.raises(ValueError, match="divisib"):
        packing.remap_shard_ops(lay, lay, old_world=2, new_world=7)

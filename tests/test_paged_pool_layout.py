"""The page-major KV pool and the kernel that copies a page at once (PR 35).

A page of the pool holds every KV head's K, then every head's V, of one
layer: ``[layers, num_pages, 2, kv_heads, page_size, head_dim]``.  The XLA
oracle still takes head-major planes, handed to it through
``heads_of_pool``, so it stays an independent check of the layout; the
kernel runs interpreted.  Blocks of 64 keys (``_BLOCK_KEYS`` lowered) so
that small contexts end inside a page, inside a block and on a block's
edge.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.inference import (ContinuousBatchingEngine, GenerationConfig,
                                  PagedKVCache)
from paddle_tpu.inference import migration as mig
from paddle_tpu.inference.generation import _cow_copy_pages
from paddle_tpu.inference.kv_spill import HostSpillPool
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

D, PAGE = 128, 16


@pytest.fixture
def small_blocks(monkeypatch):
    """KV blocks of 64 keys: four pages of 16."""
    monkeypatch.setattr(pa, "_BLOCK_KEYS", 64)


def _pool(rng, shape, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _case(rng, *, kvh, group, T, ctx, ql, dtype=jnp.float32, window=None,
          layers=None, page=PAGE, table=12):
    """One call of the interpreted kernel over a page-major pool and the
    oracle's over the head-major view of the same pool: (out, lse) twice."""
    B, n_pages, qh = len(ctx), 40, kvh * group
    qdt = jnp.float32 if dtype == jnp.int8 else dtype
    q = jnp.asarray(rng.standard_normal((B, T, qh, D)), qdt)
    kn = jnp.asarray(rng.standard_normal((B, T, kvh, D)), qdt)
    vn = jnp.asarray(rng.standard_normal((B, T, kvh, D)), qdt)
    lead = () if layers is None else (layers,)
    kv = _pool(rng, lead + (n_pages, 2, kvh, page, D), dtype)
    ks = vs = None
    if dtype == jnp.int8:
        ks = jnp.asarray(rng.uniform(0.005, 0.02, (kvh, n_pages)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, (kvh, n_pages)),
                         jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, table)), jnp.int32)
    cl, qlens = jnp.asarray(ctx, jnp.int32), jnp.asarray(ql, jnp.int32)
    layer = None if layers is None else jnp.int32(layers - 1)
    got = pa._pallas_ragged_paged_attention(
        q, kv, bt, cl, qlens, kn, vn, interpret=True, k_scale=ks,
        v_scale=vs, window=window, layer=layer)
    one = kv if layers is None else kv[layers - 1]
    f32 = (lambda a: a) if dtype == jnp.int8 else \
        (lambda a: a.astype(jnp.float32))
    k, v = pa.heads_of_pool(one)
    ref = pa._reference_ragged_paged_attention(
        q.astype(jnp.float32), f32(k), f32(v), bt, cl, qlens,
        kn.astype(jnp.float32), vn.astype(jnp.float32), k_scale=ks,
        v_scale=vs, window=window)
    return got, ref


def _assert_live_rows_match(got, ref, ql, tol):
    (out, lse), (ref_out, ref_lse) = got, ref
    assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()
    for b, n in enumerate(ql):
        if n == 0:                        # an idle slot: zeros by contract
            assert not np.asarray(out[b].astype(jnp.float32)).any()
            continue
        np.testing.assert_allclose(
            np.asarray(out[b, :n].astype(jnp.float32)),
            np.asarray(ref_out[b, :n]), rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(lse[b, :n]),
                                   np.asarray(ref_lse[b, :n]),
                                   rtol=tol, atol=tol)


def test_the_two_views_of_a_pool_are_each_others_inverse(rng):
    k = jnp.asarray(rng.standard_normal((3, 4, 5, 8, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, 4, 5, 8, 16)), jnp.float32)
    kv = pa.pool_of_heads(k, v)
    assert kv.shape == (3, 5, 2, 4, 8, 16)       # [layers, pages, K|V, ...]
    # layer 1, page 3, head 2: K then V
    assert np.array_equal(kv[1, 3, 0, 2], k[1, 2, 3])
    assert np.array_equal(kv[1, 3, 1, 2], v[1, 2, 3])
    k2, v2 = pa.heads_of_pool(kv)
    assert np.array_equal(k2, k) and np.array_equal(v2, v)
    k1, v1 = pa.heads_of_pool(kv[2])             # one layer's
    assert np.array_equal(k1, k[2]) and np.array_equal(v1, v[2])


# contexts: none, inside a page, a block's edge (64), inside the second
# block, two whole blocks, inside the third; q_lens ragged, slot 3 idle
CTX = [0, 37, 64, 100, 128, 150]


def _qlens(T):
    return [T, 1, max(1, T // 2), 0, T, min(T, 3)]


@pytest.mark.parametrize("group,T,kvh", [
    (1, 1, 1), (4, 1, 4), (5, 1, 8), (16, 1, 4),
    (1, 16, 8), (4, 16, 1), (5, 16, 4), (16, 16, 8),
    (1, 64, 4), (4, 64, 8), (5, 64, 1), (16, 64, 1)])
def test_one_program_a_slot_walks_every_head(rng, small_blocks, group, T,
                                             kvh):
    ql = _qlens(T)
    got, ref = _case(rng, kvh=kvh, group=group, T=T, ctx=CTX, ql=ql)
    _assert_live_rows_match(got, ref, ql, 5e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 1e-2),
                                       (jnp.int8, 5e-5)],
                         ids=["bfloat16", "int8"])
def test_the_pool_types_share_the_walk(rng, small_blocks, dtype, tol):
    page = 32 if dtype == jnp.int8 else PAGE
    ql = _qlens(16)
    got, ref = _case(rng, kvh=4, group=5, T=16, ctx=CTX, ql=ql, dtype=dtype,
                     page=page)
    _assert_live_rows_match(got, ref, ql, tol)


@pytest.mark.parametrize("window", [1, 20, 70, 200])
def test_a_window_starts_the_walk_at_its_first_page(rng, small_blocks,
                                                    window):
    ql = _qlens(16)
    got, ref = _case(rng, kvh=4, group=4, T=16, ctx=CTX, ql=ql,
                     window=window)
    _assert_live_rows_match(got, ref, ql, 5e-5)


@pytest.mark.parametrize("T", [1, 16])
def test_the_whole_pool_is_read_at_a_traced_layer(rng, small_blocks, T):
    ql = _qlens(T)
    got, ref = _case(rng, kvh=4, group=5, T=T, ctx=CTX, ql=ql, layers=3)
    _assert_live_rows_match(got, ref, ql, 5e-5)


def test_a_block_holds_fewer_pages_where_two_buffers_would_not_fit():
    """The block's keys follow from the shapes: 1,024 keys at 8 bf16 KV
    heads (64 KB a page, 8 MiB in two buffers), half that at 32 heads, and
    never more than the table is wide."""
    page_bytes = lambda kvh, size: 2 * kvh * PAGE * D * size   # noqa: E731
    assert pa._pages_per_block(PAGE, 264, page_bytes(8, 2)) == 64
    assert pa._pages_per_block(PAGE, 264, page_bytes(4, 2)) == 64
    assert pa._pages_per_block(PAGE, 264, page_bytes(32, 2)) == 32
    assert pa._pages_per_block(PAGE, 264, page_bytes(32, 4)) == 16
    assert pa._pages_per_block(PAGE, 40, page_bytes(8, 2)) == 40
    assert pa._pages_per_block(PAGE, 264) == 64            # the latent call


def test_page_copies_counts_whole_blocks_of_the_slots_with_work():
    """128 pages a table row, blocks of 64: a context of 1,100 tokens is
    69 pages = 2 blocks = 128 copies, one of 600 is 64, an idle slot and a
    first chunk (no context yet) start none; a window skips the pages
    behind it."""
    rows = [(1, 1100), (1, 600), (0, 900), (16, 0), (16, 1024), (1, 1025)]
    assert pa.page_copies(rows, PAGE, 128) == 128 + 64 + 0 + 0 + 64 + 128
    assert pa.page_copies(rows, PAGE, 128, window=512) == 4 * 64
    # a table of 40 pages is one block of 40
    assert pa.page_copies([(1, 600), (8, 33)], PAGE, 40) == 2 * 40
    assert pa.page_copies([], PAGE, 128) == 0


# ---------------------------------------------------------------- the pool ---

def _filled(rng, cache):
    """The cache with every array random; host copies of them."""
    new = []
    for a in cache.arrays:
        if a.dtype == jnp.int8:
            new.append(jnp.asarray(rng.integers(-127, 128, a.shape),
                                   jnp.int8))
        else:
            new.append(jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype))
    host = [np.asarray(a) for a in new]
    cache.update(*new)
    return host


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_the_pool_is_one_array_a_page_one_run(dtype):
    cache = PagedKVCache(num_layers=3, num_pages=6, page_size=8,
                         num_kv_heads=2, head_dim=16, dtype=dtype)
    assert cache.page_axis == 1
    assert cache.kv.shape == (3, 6, 2, 2, 8, 16)
    if dtype == "int8":
        kv, ks, vs = cache.arrays
        assert ks.shape == vs.shape == (3, 2, 6)     # [layers, kvh, pages]
        assert cache.page_axes == (1, 2, 2) and cache.head_axes == (2, 1, 1)
    else:
        kv, = cache.arrays
        assert cache.page_axes == (1,) and cache.head_axes == (2,)
    assert kv is cache.kv and str(cache.dtype) == dtype
    assert cache.kv.nbytes + (0 if dtype != "int8" else 2 * 3 * 2 * 6 * 4) \
        == 6 * PagedKVCache.bytes_per_page(3, 2, 8, 16, dtype)


def test_the_commit_writes_every_heads_row_of_a_token_in_one_window(rng):
    """``write_kv_pages_all_layers`` against index-by-index writes: tokens
    land at (page, offset) for every layer and head, K and V, dropped
    tokens (-1) nowhere, and nothing else moves."""
    L, P, kvh, page, d = 3, 5, 4, 8, 16
    kv0 = jnp.asarray(rng.standard_normal((L, P, 2, kvh, page, d)),
                      jnp.float32)
    k_all = jnp.asarray(rng.standard_normal((L, 6, kvh, d)), jnp.float32)
    v_all = jnp.asarray(rng.standard_normal((L, 6, kvh, d)), jnp.float32)
    slots = jnp.asarray([9, -1, 39, 0, -1, 17], jnp.int32)
    want = np.asarray(kv0).copy()
    for t, s in enumerate(np.asarray(slots)):
        if s >= 0:
            want[:, s // page, 0, :, s % page] = np.asarray(k_all)[:, t]
            want[:, s // page, 1, :, s % page] = np.asarray(v_all)[:, t]
    got = jax.jit(pa.write_kv_pages_all_layers, donate_argnums=(0,))(
        kv0, k_all, v_all, slots)
    assert np.array_equal(np.asarray(got), want)
    # one layer's scatter form agrees
    one = pa.write_kv_pages(jnp.asarray(want[0]) * 0, k_all[0], v_all[0],
                            slots)
    k, v = pa.heads_of_pool(one)
    assert np.array_equal(np.asarray(k[:, 1, 1]), np.asarray(k_all[0, 0]))
    assert np.array_equal(np.asarray(v[:, 4, 7]), np.asarray(v_all[0, 2]))


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_copy_on_write_moves_every_plane_of_a_page(rng, dtype):
    cache = PagedKVCache(num_layers=2, num_pages=6, page_size=8,
                         num_kv_heads=2, head_dim=16, dtype=dtype)
    host = _filled(rng, cache)
    src, dst = jnp.asarray([1, -1, 4], jnp.int32), \
        jnp.asarray([3, 5, 0], jnp.int32)
    out = _cow_copy_pages(cache.arrays, src, dst, cache.page_axes)
    for before, after, ax in zip(host, out, cache.page_axes):
        after = np.moveaxis(np.asarray(after), ax, 0)
        before = np.moveaxis(before, ax, 0)
        assert np.array_equal(after[3], before[1])      # copied
        assert np.array_equal(after[0], before[4])
        for p in (1, 2, 4, 5):                          # -1: a no-op
            assert np.array_equal(after[p], before[p])


def test_a_spilled_page_comes_back_with_every_heads_k_and_v(rng):
    cache = PagedKVCache(num_layers=2, num_pages=4, page_size=8,
                         num_kv_heads=2, head_dim=16, dtype="float32")
    kv, = _filled(rng, cache)
    pool = HostSpillPool(cache, capacity=1)
    pool.warm()
    assert np.array_equal(np.asarray(cache.kv), kv)     # warming wrote none
    planes = cache.page_planes(2)
    assert len(planes) == 1 and planes[0].shape == (2, 2, 2, 8, 16)
    assert np.array_equal(planes[0], kv[:, 2])          # one run a layer
    slot = pool.spill(2)
    cache.update(jnp.zeros_like(cache.kv))
    pool.swap_in(slot, 1)
    after = np.asarray(cache.kv)
    assert np.array_equal(after[:, 1], kv[:, 2])
    assert not after[:, [0, 2, 3]].any()


def test_a_snapshots_planes_stay_head_major_on_the_wire(rng):
    """The pool's page ``[layers, 2, kv_heads, page, d]`` goes out as ``(k,
    v)``, each ``[layers, kv_heads, page, d]``, as before the pool was
    page-major: stored snapshots keep their meaning and digest."""
    kv = rng.standard_normal((3, 2, 4, 8, 16)).astype(np.float32)
    ks = rng.uniform(0.1, 1.0, (3, 4)).astype(np.float32)
    wire = mig._wire_planes((kv, ks, ks + 1))
    assert [p.shape for p in wire] == [(3, 4, 8, 16), (3, 4, 8, 16),
                                       (3, 4), (3, 4)]
    assert np.array_equal(wire[0], kv[:, 0]) and \
        np.array_equal(wire[1], kv[:, 1])
    back = mig._pool_planes(wire)
    assert np.array_equal(back[0], kv) and np.array_equal(back[2], ks + 1)
    assert len(mig._wire_planes((kv,))) == 2            # a float pool


def _engine(model, **kw):
    return ContinuousBatchingEngine(
        model, max_batch=2, gen=GenerationConfig(max_new_tokens=8,
                                                 do_sample=False),
        max_seq_len=64, page_size=8, prefill_bucket=8, num_pages=12,
        prefix_cache=True, **kw)


def test_a_migrated_session_installs_every_heads_k_and_v_of_its_pages():
    """Export from one engine, import into another: the pages the
    successor indexed hold, head by head, the bytes the snapshot carried,
    and the continuation's tokens are the source's."""
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    S = list(range(1, 25))                              # three pages of 8
    src = _engine(model)
    r0 = src.add_request(S + [30])
    first = src.run()[r0]
    snap = mig.to_wire(mig.export_session(src, tokens=S))
    assert len(snap["pages"]) == 3
    dst = _engine(model)
    res = mig.import_session(dst, snap)
    assert res["imported"] == 3
    k, v = pa.heads_of_pool(dst.g.cache.kv)             # [L, kvh, P, ..]
    live = mig.from_wire(snap)
    for node, pg in zip(dst.prefix_cache.chain(S), live["pages"]):
        assert np.array_equal(pg["planes"][0], np.asarray(k[:, :, node.page]))
        assert np.array_equal(pg["planes"][1], np.asarray(v[:, :, node.page]))
    r1 = dst.add_request(S + [30])
    assert dst.run()[r1] == first
    assert dst.g.cache.allocator.prefix_tokens_saved >= 16


def test_the_kernel_under_a_mesh_reads_its_shards_heads_of_every_page(rng):
    """Two CPU devices, the pool sharded on its KV-head axis (axis 2 of one
    layer's ``[pages, 2, kv_heads, ...]``): each shard's program walks its
    own heads of the same pages, and the gathered result is the oracle's
    over the whole pool."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    kvh, group, T, B = 4, 2, 8, 3
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
    q = jnp.asarray(rng.standard_normal((B, T, kvh * group, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, T, kvh, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, T, kvh, D)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((20, 2, kvh, PAGE, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, 20, (B, 6)), jnp.int32)
    cl = jnp.asarray([0, 37, 80], jnp.int32)
    ql = jnp.asarray([T, 1, 5], jnp.int32)

    def body(q_, kv_, kn_, vn_):
        i = jax.lax.axis_index("mp")
        sl = lambda a, n: jax.lax.dynamic_slice_in_dim(    # noqa: E731
            a, i * n, n, axis=2)
        out = pa._pallas_ragged_paged_attention(
            sl(q_, kvh // 2 * group), kv_, bt, cl, ql, sl(kn_, kvh // 2),
            sl(vn_, kvh // 2), interpret=True)[0]
        return jax.lax.all_gather(out, "mp", axis=2, tiled=True)

    pool = P(None, None, "mp")
    kv_sharded = jax.device_put(kv, NamedSharding(mesh, pool))
    got = jax.jit(jax.shard_map(body, mesh=mesh,
                                in_specs=(P(), pool, P(), P()),
                                out_specs=P(), check_vma=False))(
        q, kv_sharded, kn, vn)
    ref, _ = pa._reference_ragged_paged_attention(
        q, *pa.heads_of_pool(kv), bt, cl, ql, kn, vn)
    for b, n in enumerate([T, 1, 5]):
        np.testing.assert_allclose(np.asarray(got[b, :n]),
                                   np.asarray(ref[b, :n]),
                                   rtol=5e-5, atol=5e-5)


def test_the_registry_says_what_one_copy_moves():
    """``serving.kv_copy_bytes``: a page's K and V of every KV head, one
    layer, as the engine's pool holds them."""
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        model, max_batch=2, max_seq_len=64, page_size=8, prefill_bucket=8,
        metrics=True)
    want = 2 * cfg.num_key_value_heads * 8 * cfg.head_dim \
        * eng.g.cache.kv.dtype.itemsize
    assert eng.g.kv_copy_bytes == want == eng.g.cache.kv[0, 0].nbytes
    assert obs.metrics.gauge("serving.kv_copy_bytes").value == want

"""Grouped (ragged) expert GEMM: kernel numerics in interpret mode, the
sorted-dispatch plan's invariants, the grouped MoE forward/backward vs a
dense no-capacity oracle, and TPU Mosaic cross-lowering at bench-like
shapes (reference surface: paddle/phi/kernels/fusion/ grouped MoE GEMMs,
incubate fused_moe)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.kernels import grouped_matmul as G
from paddle_tpu.models import llama as L


@pytest.fixture
def interp():
    flags.set_flags({"FLAGS_grouped_matmul_interpret": True})
    yield
    flags.set_flags({"FLAGS_grouped_matmul_interpret": False})


def _rand(shape, scale=1.0, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape) * scale,
        jnp.float32)


class TestKernels:
    M, K, N, E, bm = 32, 128, 256, 3, 8
    tg = jnp.asarray([0, 0, 1, 2], jnp.int32)

    def test_gmm_matches_reference(self, interp):
        lhs = _rand((self.M, self.K))
        rhs = _rand((self.E, self.K, self.N), seed=1)
        out = G.gmm(lhs, rhs, self.tg, bm=self.bm)
        ref = G._gmm_reference(lhs, rhs, self.tg, bm=self.bm)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_gmm_trans_rhs(self, interp):
        lhs = _rand((self.M, self.K))
        rhs = _rand((self.E, self.K, self.N), seed=1)
        out = G.gmm(lhs, jnp.swapaxes(rhs, 1, 2), self.tg, bm=self.bm,
                    trans_rhs=True)
        ref = G._gmm_reference(lhs, rhs, self.tg, bm=self.bm)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_tgmm_matches_reference(self, interp):
        lhs = _rand((self.M, self.K))
        rhs = _rand((self.M, self.N), seed=1)
        out = G.tgmm(lhs, rhs, self.tg, self.E, bm=self.bm)
        ref = G._tgmm_reference(lhs, rhs, self.tg, self.E, bm=self.bm)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_grouped_matmul_grads(self, interp):
        lhs = _rand((self.M, self.K))
        rhs = _rand((self.E, self.K, self.N), seed=1)
        dy = _rand((self.M, self.N), seed=2)

        def f(l, r):
            return (G.grouped_matmul(l, r, self.tg, self.E, self.bm,
                                     512, 512) * dy).sum()

        def fr(l, r):
            return (G._gmm_reference(l, r, self.tg, bm=self.bm) * dy).sum()

        gl, gr = jax.grad(f, (0, 1))(lhs, rhs)
        gl_r, gr_r = jax.grad(fr, (0, 1))(lhs, rhs)
        np.testing.assert_allclose(gl, gl_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(gr, gr_r, rtol=1e-4, atol=1e-4)

    def test_empty_group_gets_a_tile(self, interp):
        # expert 1 receives zero tokens; the plan still assigns it a tile
        # and tgmm writes zeros (not garbage) for its weight grad
        ids = jnp.asarray([0, 0, 2, 2, 2, 0, 2, 0], jnp.int32)
        inv, pos, tg = G.sorted_dispatch_plan(ids, 3, bm=8)
        assert set(np.asarray(tg)) == {0, 1, 2}
        lhs = jnp.zeros((tg.shape[0] * 8, 128), jnp.float32)
        out = G.tgmm(lhs, jnp.zeros((tg.shape[0] * 8, 128), jnp.float32),
                     tg, 3, bm=8)
        assert not np.isnan(np.asarray(out)).any()
        np.testing.assert_array_equal(np.asarray(out[1]), 0.0)


class TestTileSelection:
    """Explicit bn/bk > autotune cache > sweep flags > 512 default; flag
    values that cannot tile the backward shapes fail fast at forward
    time with the flag named."""

    @pytest.fixture(autouse=True)
    def _isolated_autotune(self, tmp_path):
        from paddle_tpu.kernels import autotune
        flags.set_flags({"autotune_cache_path": str(tmp_path / "at.json")})
        autotune.clear()
        yield
        autotune.clear()
        flags.set_flags({"autotune_cache_path": ""})

    def test_explicit_args_beat_flags(self, interp):
        lhs = _rand((32, 128))
        rhs = _rand((3, 128, 256), seed=1)
        tg = jnp.asarray([0, 0, 1, 2], jnp.int32)
        # 192 tiles neither 128 nor 256 -> the flag default would raise,
        # but an explicit bn/bk must win and succeed
        flags.set_flags({"FLAGS_grouped_matmul_bn": 192,
                         "FLAGS_grouped_matmul_bk": 192})
        try:
            out = G.gmm(lhs, rhs, tg, bm=8, bn=128, bk=128)
            ref = G._gmm_reference(lhs, rhs, tg, bm=8)
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
            with pytest.raises(ValueError):
                G.gmm(lhs, rhs, tg, bm=8)      # flag default path raises
        finally:
            flags.set_flags({"FLAGS_grouped_matmul_bn": 0,
                             "FLAGS_grouped_matmul_bk": 0})

    def test_bad_flag_fails_fast_with_flag_named(self, interp):
        lhs = _rand((32, 128))
        rhs = _rand((3, 128, 256), seed=1)
        tg = jnp.asarray([0, 0, 1, 2], jnp.int32)
        flags.set_flags({"FLAGS_grouped_matmul_bk": 192})
        try:
            with pytest.raises(ValueError, match="grouped_matmul_bk"):
                G.grouped_matmul(lhs, rhs, tg, 3, 8)
        finally:
            flags.set_flags({"FLAGS_grouped_matmul_bk": 0})

    def test_autotune_cache_beats_flag_default(self):
        from paddle_tpu.kernels import autotune

        key = autotune.make_key("grouped_matmul_gmm", M=32, K=128, N=256,
                                E=3, bm=8, dtype="float32")
        autotune.record(key, (128, 128))
        try:
            flags.set_flags({"FLAGS_grouped_matmul_bn": 256})
            bn, bk = G._resolve_tiles("gmm", 32, 128, 256, 3, 8,
                                      jnp.float32, None, None, "interpret")
            assert (bn, bk) == (128, 128)      # measured entry wins
            bn, bk = G._resolve_tiles("gmm", 32, 128, 256, 3, 8,
                                      jnp.float32, 256, None, "interpret")
            assert bn == 256                   # explicit beats everything
        finally:
            flags.set_flags({"FLAGS_grouped_matmul_bn": 0})
            autotune.clear()

    def test_candidates_respect_divisibility(self):
        from paddle_tpu.kernels import autotune

        cands = autotune.grouped_matmul_candidates(512, 384, 256)
        assert cands and all(256 % bn == 0 and 384 % bk == 0
                             for bn, bk in cands)
        assert (256, 128) in cands


class TestDispatchPlan:
    def test_plan_invariants(self):
        rng = np.random.default_rng(0)
        for E, F, bm in ((4, 64, 8), (8, 256, 16), (3, 31, 8)):
            ids = jnp.asarray(rng.integers(0, E, F), jnp.int32)
            inv, pos, tg = G.sorted_dispatch_plan(ids, E, bm)
            inv, pos, tg = map(np.asarray, (inv, pos, tg))
            M = inv.shape[0]
            assert M % bm == 0 and tg.shape[0] == M // bm
            # tile groups nondecreasing and every group owns >= 1 tile
            assert (np.diff(tg) >= 0).all()
            assert set(tg) == set(range(E))
            # pos/inv are inverse on the occupied rows
            assert (inv[pos] == np.arange(F)).all()
            occupied = inv[inv < F]
            assert len(set(occupied)) == F  # no slot collisions
            # every occupied row sits in a tile owned by its expert
            row_expert = tg[pos // bm]
            assert (row_expert == np.asarray(ids)).all()

    def test_plan_is_stable_within_expert(self):
        ids = jnp.asarray([1, 0, 1, 0, 1], jnp.int32)
        inv, pos, tg = G.sorted_dispatch_plan(ids, 2, bm=8)
        pos = np.asarray(pos)
        # tokens of the same expert keep arrival order
        assert pos[1] < pos[3]          # expert-0 entries
        assert pos[0] < pos[2] < pos[4]  # expert-1 entries


def _plan_by_hand(ids, E, bm, real):
    """The plan row by row: the real entries of expert 0 in arrival order,
    padded to whole tiles and to at least one, then expert 1's, ..."""
    F = len(ids)
    M = -(-F // bm) * bm + E * bm
    inv, pos, tg = np.full(M, F), np.full(F, M), []
    row = 0
    for e in range(E):
        mine = [f for f in range(F) if real[f] and ids[f] == e]
        for i, f in enumerate(mine):
            inv[row + i], pos[f] = f, row + i
        tiles = max(-(-len(mine) // bm), 1)
        tg += [e] * tiles
        row += tiles * bm
    return inv, pos, tg, M


MASKS = {
    "all_live": lambda F, k: np.ones(F, bool),
    "one_token_live": lambda F, k: np.arange(F) // k == 3,
    "a_third_live": lambda F, k: (np.arange(F) // k) % 3 == 0,
    "none_live": lambda F, k: np.zeros(F, bool),
}


@pytest.mark.parametrize("ids_of", ["spread", "one_expert"])
@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("bm", [8, 64, 512])
def test_plan_with_a_mask_lays_out_the_real_entries_alone(bm, mask, ids_of):
    """``masked_dispatch_plan``: a dropped entry takes no row and its
    ``pos`` is the sentinel M; every expert still owns a tile; the tiles
    after the last expert's rows name the last live tile, negated; the
    fourth result counts the live ones, the fifth each expert's real
    entries.  ``sorted_dispatch_plan`` (no
    mask): the three results it has always had."""
    E, k, F = 8, 2, 2 * 296
    rng = np.random.default_rng(bm)
    ids = rng.integers(0, E, F) if ids_of == "spread" else np.full(F, 5)
    real = MASKS[mask](F, k)
    want_inv, want_pos, want_tg, M = _plan_by_hand(ids, E, bm, real)
    inv, pos, tg, live, counts = map(np.asarray, G.masked_dispatch_plan(
        jnp.asarray(ids, jnp.int32), jnp.asarray(real), E, bm))
    assert inv.shape == (M,) and M == -(-F // bm) * bm + E * bm
    assert live == len(want_tg) and E <= live <= M // bm
    assert (inv == want_inv).all() and (pos == want_pos).all()
    assert (pos[~real] == M).all() and (pos[real] < live * bm).all()
    assert tg[:live].tolist() == want_tg
    assert (tg[live:] == -live).all()      # park on tile ``live - 1``
    assert counts.tolist() == np.bincount(ids[real], minlength=E).tolist()
    if mask == "all_live":
        plain = G.sorted_dispatch_plan(jnp.asarray(ids, jnp.int32), E, bm)
        assert len(plain) == 3
        p_inv, p_pos, p_tg = map(np.asarray, plain)
        assert (p_inv == want_inv).all() and (p_pos == want_pos).all()
        assert p_tg[:live].tolist() == want_tg
        assert (p_tg[live:] == E - 1).all()


def _dense_oracle(x, gw, wg, wu, wd, k):
    """No-capacity routed mixture: what grouped must reproduce exactly."""
    B, S, H = x.shape
    E = gw.shape[-1]
    xf = x.reshape(-1, H)
    probs = jax.nn.softmax(xf @ gw, -1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / topv.sum(-1, keepdims=True)
    comb = jnp.zeros_like(probs).at[
        jnp.arange(xf.shape[0])[:, None], topi].set(topv)
    h = jax.nn.silu(jnp.einsum("nh,ehi->eni", xf, wg)) * \
        jnp.einsum("nh,ehi->eni", xf, wu)
    oe = jnp.einsum("eni,eih->enh", h, wd)
    y = jnp.einsum("ne,enh->nh", comb, oe).reshape(B, S, H)
    me = probs.mean(0)
    ce = jnp.zeros((E,)).at[topi[:, 0]].add(1.0) / xf.shape[0]
    return y, E * jnp.sum(me * ce)


class TestMoEGrouped:
    B, S, H, I, E, k = 2, 16, 64, 96, 4, 2

    def _weights(self):
        return (_rand((self.H, self.E), 0.1, 1),
                _rand((self.E, self.H, self.I), 0.05, 2),
                _rand((self.E, self.H, self.I), 0.05, 3),
                _rand((self.E, self.I, self.H), 0.05, 4))

    def test_forward_matches_dense_oracle(self):
        x = _rand((self.B, self.S, self.H))
        gw, wg, wu, wd = self._weights()
        y, aux, stats = L.moe_mlp_forward_grouped(
            x, gw, wg, wu, wd, top_k=self.k, block_m=8)
        yr, auxr = _dense_oracle(x, gw, wg, wu, wd, self.k)
        np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(aux, auxr, rtol=1e-5)
        assert float(stats[0]) == 1.0  # nothing drops

    def test_grads_match_dense_oracle(self):
        x = _rand((self.B, self.S, self.H))
        weights = self._weights()

        def f(x_, *ws):
            y, aux, _ = L.moe_mlp_forward_grouped(
                x_, ws[0], ws[1], ws[2], ws[3], top_k=self.k, block_m=8)
            return (y * 0.1).sum() + aux

        def fr(x_, *ws):
            y, aux = _dense_oracle(x_, ws[0], ws[1], ws[2], ws[3], self.k)
            return (y * 0.1).sum() + aux

        g = jax.grad(f, tuple(range(5)))(x, *weights)
        gr = jax.grad(fr, tuple(range(5)))(x, *weights)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    def test_pallas_path_full_ffn(self, interp):
        # H/I at lane multiples so the real kernel code runs (interpret)
        B, S, H, I, E, k = 1, 8, 128, 256, 2, 2
        x = _rand((B, S, H))
        gw = _rand((H, E), 0.1, 1)
        wg = _rand((E, H, I), 0.05, 2)
        wu = _rand((E, H, I), 0.05, 3)
        wd = _rand((E, I, H), 0.05, 4)
        y, aux, _ = L.moe_mlp_forward_grouped(x, gw, wg, wu, wd,
                                              top_k=k, block_m=8)
        yr, _ = _dense_oracle(x, gw, wg, wu, wd, k)
        np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-5)

    def test_train_step_grouped_dispatch(self):
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep
        import dataclasses

        cfg = LlamaConfig.mixtral_tiny()
        cfg = dataclasses.replace(cfg, moe_dispatch="grouped",
                                  moe_block_m=8)
        ps = PretrainStep(cfg, ParallelConfig(remat=False, loss_chunks=1))
        state = ps.init_state(seed=0)
        rng = np.random.default_rng(0)
        ids, labels = ps.shard_batch(
            rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32),
            rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32))
        losses = []
        for _ in range(4):
            state, loss = ps.train_step(state, ids, labels)
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


def _grouped_under_capacity(x, gw, wg, wu, wd, *, k, cf, bm):
    """``_grouped_ffn`` over the entries a capacity keeps (the k-major
    priority of the gather formulation).  Every token stands ``k`` times in
    the padded layout; a dropped entry goes to a trailing discard group:
    its padded row reads the zero row, its ``pos`` is the sentinel ``M``
    and its gate is zero, as the sharded path lays them out."""
    B, S, H = x.shape
    N, E = B * S, gw.shape[-1]
    xf = x.reshape(N, H)
    topv, topi, _, _ = L._route_topk(xf, gw, k)
    cap = max(1, int(N * k * cf / E))
    keep = G.capacity_dispatch_plan(topi, topv, E, cap)[3].reshape(k, N).T
    kept = keep.reshape(N * k)
    inv, pos, tg = G.sorted_dispatch_plan(
        jnp.where(keep, topi, E).reshape(N * k), E + 1, bm)
    inv = jnp.where((inv < N * k)
                    & jnp.take(kept, jnp.minimum(inv, N * k - 1)),
                    inv, N * k)
    pos = jnp.where(kept, pos, inv.shape[0])
    y = L._grouped_ffn(xf, wg, wu, wd, topv * keep, inv, pos,
                       jnp.minimum(tg, E - 1), E, k, bm)
    return y.reshape(B, S, H), keep


@pytest.mark.parametrize("what", ["value", "gradient"])
@pytest.mark.parametrize("cf", [8.0, 0.5], ids=["dropless", "drops"])
@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_grouped_ffn_takes_and_scales_its_rows_as_the_gather_reference(
        request, kernel, cf, what):
    """The dispatch's take (each token ``k`` times, dropped entries as zero
    rows) and the backward's gate scaling live in ``_grouped_ffn_fwd`` /
    ``_grouped_ffn_bwd``, around kernels that multiply what they are given:
    value and every gradient agree with the capacity-gather formulation
    under the same router, with top-2 gates that are not uniform."""
    B, S, H, I, E, k, bm = 2, 8, 128, 256, 4, 2, 8
    x = _rand((B, S, H))
    weights = (_rand((H, E), 0.3, 1), _rand((E, H, I), 0.05, 2),
               _rand((E, H, I), 0.05, 3), _rand((E, I, H), 0.05, 4))
    dy = _rand((B, S, H), seed=5)

    def grouped(x_, *ws):
        return _grouped_under_capacity(x_, *ws, k=k, cf=cf, bm=bm)[0]

    def gather(x_, *ws):
        return L.moe_mlp_forward(x_, *ws, top_k=k, capacity_factor=cf)[0]

    keep = np.asarray(_grouped_under_capacity(x, *weights, k=k, cf=cf,
                                              bm=bm)[1])
    assert keep.all() == (cf == 8.0)       # "drops" really drops entries
    gates = np.asarray(L._route_topk(x.reshape(-1, H), weights[0], k)[0])
    assert np.abs(gates[:, 0] - gates[:, 1]).min() > 1e-3
    if kernel == "interpret":
        request.getfixturevalue("interp")
    if what == "value":
        np.testing.assert_allclose(grouped(x, *weights), gather(x, *weights),
                                   rtol=1e-4, atol=1e-5)
        return
    loss = lambda f: lambda *a: (f(*a) * dy).sum()
    got = jax.grad(loss(grouped), tuple(range(5)))(x, *weights)
    want = jax.grad(loss(gather), tuple(range(5)))(x, *weights)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


class TestMoEGroupedSharded:
    """shard_map formulation on the dp x ep x mp virtual mesh: replicated
    router, ragged local GEMM over each shard's expert bank, one psum."""

    B, S, H, I, E, k = 4, 8, 64, 128, 4, 2

    def _mesh(self):
        from jax.sharding import Mesh
        return Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                    ("dp", "ep", "mp"))

    def _inputs(self):
        x = _rand((self.B, self.S, self.H), 0.5)
        gw = _rand((self.H, self.E), 0.1, 1)
        wg = _rand((self.E, self.H, self.I), 0.05, 2)
        wu = _rand((self.E, self.H, self.I), 0.05, 3)
        wd = _rand((self.E, self.I, self.H), 0.05, 4)
        return x, gw, wg, wu, wd

    def test_fwd_and_grads_match_single_device(self):
        mesh = self._mesh()
        x, gw, wg, wu, wd = self._inputs()

        def sharded(x_, gw_, wg_, wu_, wd_):
            # cf high enough that nothing drops -> exact parity
            return L.moe_mlp_forward_grouped_sharded(
                x_, gw_, wg_, wu_, wd_, mesh=mesh, top_k=self.k,
                block_m=8, capacity_factor=8.0)

        y, aux, stats = jax.jit(sharded)(x, gw, wg, wu, wd)
        yr, auxr, _ = L.moe_mlp_forward_grouped(
            x, gw, wg, wu, wd, top_k=self.k, block_m=8)
        np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-6)
        assert float(stats[0]) == 1.0

        # grads through the FFN path match exactly (the aux term is the
        # per-dp-shard mean, a deliberate semantic difference, so it is
        # excluded from the parity check)
        def f(fn):
            def loss(x_, wg_, wu_, wd_, gw_):
                y, _, _ = fn(x_, gw_, wg_, wu_, wd_)
                return (y * 0.1).astype(jnp.float32).sum()
            return jax.grad(loss, (0, 1, 2, 3, 4))

        g = jax.jit(f(sharded))(x, wg, wu, wd, gw)
        gr = f(lambda *a: L.moe_mlp_forward_grouped(
            a[0], a[1], a[2], a[3], a[4], top_k=self.k, block_m=8))(
            x, wg, wu, wd, gw)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)

    def test_capacity_drops_are_reported(self):
        mesh = self._mesh()
        _, gw, wg, wu, wd = self._inputs()
        # enough tokens that the row budget (cf * kN/ep + alignment
        # slack) genuinely overflows
        x = _rand((self.B, 64, self.H), 0.5)

        def sharded(x_, gw_, wg_, wu_, wd_):
            return L.moe_mlp_forward_grouped_sharded(
                x_, gw_, wg_, wu_, wd_, mesh=mesh, top_k=self.k,
                block_m=8, capacity_factor=0.25)   # force overflow

        y, aux, stats = jax.jit(sharded)(x, gw, wg, wu, wd)
        assert np.isfinite(np.asarray(y)).all()
        assert 0.0 < float(stats[0]) < 1.0         # kept_frac < 1


class TestMosaicLowering:
    """Bench-shaped cross-lowering: catches chip-only Mosaic bugs on CPU
    (same pattern as tests/test_mosaic_lowering.py)."""

    def test_grouped_ffn_lowers_fwd_bwd(self):
        B, S, H, I, E, k, bm = 2, 256, 1024, 2816, 8, 2, 512
        x = jnp.zeros((B, S, H), jnp.bfloat16)
        gw = jnp.zeros((H, E), jnp.bfloat16)
        wg = jnp.zeros((E, H, I), jnp.bfloat16)
        wu = jnp.zeros((E, H, I), jnp.bfloat16)
        wd = jnp.zeros((E, I, H), jnp.bfloat16)

        def loss(x_, wg_, wu_, wd_, gw_):
            y, aux, _ = L.moe_mlp_forward_grouped(
                x_, gw_, wg_, wu_, wd_, top_k=k, block_m=bm)
            return y.astype(jnp.float32).sum() + aux

        jax.export.export(jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))),
                          platforms=["tpu"])(x, wg, wu, wd, gw)


@pytest.mark.parametrize("told", ["count", "table"])
@pytest.mark.parametrize("trans_rhs", [False, True], ids=["plain", "trans"])
@pytest.mark.parametrize("live", [1, 3, 6, 10])
def test_gmm_live_tiles_multiplies_only_the_live_prefix(live, trans_rhs,
                                                        told):
    """``gmm(live_tiles=)`` (a chip that holds a share of the experts sorts
    the other experts' entries last) and ``gmm(dead_in_table=True)`` (the
    plan dropped the rows without a token; the table's dead entries name
    the last live tile, negated): the first ``live`` row tiles are the
    plain product; the rest are skipped, whatever their number, and only
    the live rows are ever read."""
    rng = np.random.default_rng(0)
    bm, K, N, E = 8, 128, 256, 4
    M = 10 * bm
    lhs = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    shape = (E, N, K) if trans_rhs else (E, K, N)
    rhs = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    tg = jnp.asarray([0, 0, 1, 2, 2, 3, 3, 3, 3, 3], jnp.int32)
    want = np.asarray(G._gmm_reference(lhs, rhs, tg, bm=bm,
                                       trans_rhs=trans_rhs))

    def call(n):
        how = {"live_tiles": n} if told == "count" \
            else {"dead_in_table": True}
        table = tg if told == "count" else \
            jnp.where(jnp.arange(10) < n, tg, -n)
        return G.gmm(lhs, rhs, table, bm=bm, bn=128, bk=128,
                     trans_rhs=trans_rhs, interpret=True, **how)

    got = np.asarray(jax.jit(call)(jnp.int32(live)))
    np.testing.assert_allclose(got[:live * bm], want[:live * bm],
                               rtol=1e-5, atol=1e-5)
    if live == 10:          # all live: the call without the argument
        plain = np.asarray(G.gmm(lhs, rhs, tg, bm=bm, bn=128, bk=128,
                                 trans_rhs=trans_rhs, interpret=True))
        assert np.array_equal(got, plain)

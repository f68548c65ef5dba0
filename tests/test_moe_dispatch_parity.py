"""MoE dispatch-mode parity smoke + overflow-regime gradient regression.

gather / einsum / grouped (``LlamaConfig.moe_dispatch``) are three
formulations of the same routed mixture.  This file is the tier-1 guard
for that equivalence:

- the fast smoke: all three modes, tiny E/H, forward AND backward
  allclose against the einsum oracle at no-drop capacity — catches any
  future dispatch regression without the slow mesh tests;
- the overflow regime (kept_frac < 1): finite-difference gradient parity
  and EXACTLY-zero FFN gradient for dropped tokens, for gather, einsum,
  grouped and grouped_sharded.  This is the regression test for a
  PR 1 finding: the sharded grouped path used to clamp dropped
  entries' buffer positions to a real row, silently accumulating a kept
  row's gradient into unrelated tokens under capacity overflow.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.grouped_matmul import sorted_dispatch_plan
from paddle_tpu.models import llama as L


def _rand(shape, scale, seed, dtype=jnp.float32):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape) * scale, dtype)


def _inputs(B, S, H, I, E, dtype=jnp.float32):
    return (_rand((B, S, H), 0.5, 0, dtype),
            _rand((H, E), 0.1, 1, dtype),
            _rand((E, H, I), 0.05, 2, dtype),
            _rand((E, H, I), 0.05, 3, dtype),
            _rand((E, I, H), 0.05, 4, dtype))


class TestDispatchParitySmoke:
    """All three modes vs the einsum oracle, fwd + bwd, no drops."""

    B, S, H, I, E, k = 2, 8, 16, 32, 4, 2

    def _modes(self):
        cf = float(self.E)       # capacity >= E: nothing drops anywhere
        return {
            "gather": lambda x, gw, wg, wu, wd: L.moe_mlp_forward(
                x, gw, wg, wu, wd, top_k=self.k, capacity_factor=cf),
            "einsum": lambda x, gw, wg, wu, wd: L.moe_mlp_forward_einsum(
                x, gw, wg, wu, wd, top_k=self.k, capacity_factor=cf,
                groups=1),
            "grouped": lambda x, gw, wg, wu, wd: L.moe_mlp_forward_grouped(
                x, gw, wg, wu, wd, top_k=self.k, block_m=8),
        }

    def test_forward_parity(self):
        x, gw, wg, wu, wd = _inputs(self.B, self.S, self.H, self.I, self.E)
        modes = self._modes()
        y_ref, aux_ref, _ = modes["einsum"](x, gw, wg, wu, wd)
        for name in ("gather", "grouped"):
            y, aux, stats = modes[name](x, gw, wg, wu, wd)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"mode={name}")
            np.testing.assert_allclose(float(aux), float(aux_ref),
                                       rtol=1e-5)
            assert float(stats[0]) == 1.0     # no drops at this capacity

    def test_backward_parity(self):
        x, gw, wg, wu, wd = _inputs(self.B, self.S, self.H, self.I, self.E)
        r = _rand((self.B, self.S, self.H), 1.0, 9)
        modes = self._modes()

        def grads(fn):
            def loss(x_, gw_, wg_, wu_, wd_):
                y, aux, _ = fn(x_, gw_, wg_, wu_, wd_)
                return (y * r).sum() + aux
            return jax.grad(loss, (0, 1, 2, 3, 4))(x, gw, wg, wu, wd)

        g_ref = grads(modes["einsum"])
        for name in ("gather", "grouped"):
            g = grads(modes[name])
            for a, b, wname in zip(g, g_ref, ("x", "gate_w", "w_gate",
                                              "w_up", "w_down")):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                    err_msg=f"mode={name} d{wname}")


class TestServingDispatch:
    """The serving prefill MoE FFN routes through the grouped kernels
    when the config says grouped; decode-sized inputs stay on the dense
    scan.  Both must match the dense-mixture oracle exactly."""

    H, E, I, k = 16, 4, 32, 2

    def _lp(self):
        return {
            "mlp.gate.weight": _rand((self.H, self.E), 0.1, 1),
            "mlp.experts_gate": _rand((self.E, self.H, self.I), 0.05, 2),
            "mlp.experts_up": _rand((self.E, self.H, self.I), 0.05, 3),
            "mlp.experts_down": _rand((self.E, self.I, self.H), 0.05, 4),
        }

    def _moe(self, dispatch, block_m=128):
        from paddle_tpu.models.decoder_spec import MoeSpec
        return MoeSpec(num_experts=self.E, top_k=self.k, dispatch=dispatch,
                       block_m=block_m)

    def test_prefill_grouped_matches_dense(self):
        from paddle_tpu.inference.generation import _moe_ffn

        lp = self._lp()
        y = _rand((2, 32, self.H), 0.5, 8)
        grouped, _ = _moe_ffn(y, lp, self._moe("grouped", 8))
        dense, _ = _moe_ffn(y, lp, self._moe("dense"))
        np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)

    def test_decode_sized_input_stays_dense(self):
        from paddle_tpu.inference.generation import _moe_ffn

        lp = self._lp()
        y = _rand((2, self.H), 0.5, 8)     # 2 rows * k=2 < block_m=128
        out, _ = _moe_ffn(y, lp, self._moe("grouped", 128))
        dense, _ = _moe_ffn(y, lp, self._moe("dense"))
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=1e-6)


    @pytest.mark.parametrize("layered", [False, True],
                             ids=["one_bank", "switch_over_layers"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bf16"])
    def test_rows_without_a_token_are_routed_nowhere(self, dtype, layered):
        """The whole-bank grouped arm with ``live`` (the kernel interpreted,
        dead tiles named by the table): a live row's output has the bits of
        the unmasked call's, a row without a token reads zero, and ``rows``
        counts the real entries and the rows of the tiles they fill."""
        from paddle_tpu import flags
        from paddle_tpu.inference.generation import EXPERT_BANKS, _moe_ffn

        H = I = 128
        E, k, bm, N = self.E, self.k, 8, 48
        lp = {"mlp.gate.weight": _rand((H, E), 0.1, 1, dtype)}
        shapes = [(E, H, I), (E, H, I), (E, I, H)]
        for i, (name, shape) in enumerate(zip(EXPERT_BANKS, shapes)):
            bank = _rand(shape, 0.05, 2 + i, dtype)
            # the layer's bank second of two: the first must not be read
            lp[name] = (jnp.full(shape, jnp.nan, dtype), bank) if layered \
                else bank
        layer = jnp.int32(1) if layered else None
        y = _rand((1, N, H), 0.5, 8, dtype)
        live = np.zeros(N, bool)
        live[[0, 1, 2, 3, 4, 17, 30, 31, 47]] = True
        moe = self._moe("grouped", bm)
        flags.set_flags({"FLAGS_grouped_matmul_interpret": True})
        try:
            masked, rows = _moe_ffn(y, lp, moe, live=jnp.asarray(live),
                                    layer=layer)
            plain, rows_plain = _moe_ffn(y, lp, moe, layer=layer)
        finally:
            flags.set_flags({"FLAGS_grouped_matmul_interpret": False})
        masked, plain = (np.asarray(a[0].astype(jnp.float32))
                         for a in (masked, plain))
        assert np.isfinite(plain).all() and np.abs(plain).max() > 0
        assert np.array_equal(masked[live], plain[live])
        assert not masked[~live].any()
        _, topi, _, _ = L._route_topk(y[0], lp["mlp.gate.weight"], k)
        per = np.bincount(np.asarray(topi)[live].ravel(), minlength=E)
        tiles = np.maximum(-(-per // bm), 1).sum()
        assert rows.tolist() == [k * live.sum(), tiles * bm, per.max()]
        assert rows_plain[0] == k * N and rows[1] < rows_plain[1] <= \
            N * k + E * bm


def _keep_mask_global(x, gw, k, E, cf):
    """The (token, choice) keep mask of the global-capacity (gather /
    einsum G=1) formulations — the same k-major cumsum-slot computation
    the paths run."""
    B, S, H = x.shape
    N = B * S
    xf = x.reshape(N, H)
    _, topi, _, _ = L._route_topk(xf, gw, k)
    cap = max(1, int(N * k * cf / E))
    idx_flat = np.asarray(topi).T.reshape(k * N)
    oh = np.eye(E)[idx_flat]
    pos = (np.cumsum(oh, axis=0) * oh - oh).sum(-1)
    keep = pos < cap                                    # [k*N], k-major
    return keep.reshape(k, N).T                         # [N, k]


def _keep_mask_sharded(x, gw, k, E, ep, dp, bm, cf):
    """The keep mask of moe_mlp_forward_grouped_sharded: per dp shard the
    router runs on the local tokens; per ep shard, owned entries keep iff
    their sorted-plan row survives the m_cap truncation."""
    B, S, H = x.shape
    keep_all = np.zeros((B * S, k), bool)
    nb = B // dp
    for di in range(dp):
        xf = np.asarray(x[di * nb:(di + 1) * nb]).reshape(-1, H)
        n = xf.shape[0]
        _, topi, _, _ = L._route_topk(jnp.asarray(xf), gw, k)
        topi = np.asarray(topi)
        E_loc = E // ep
        m_cap = -(-int(n * k * cf / ep) // bm) * bm + E_loc * bm
        for ei in range(ep):
            own = (topi // E_loc) == ei                 # [n, k]
            local_e = np.where(own, topi % E_loc, E_loc).reshape(n * k)
            inv, pos, tg = sorted_dispatch_plan(
                jnp.asarray(local_e, jnp.int32), E_loc + 1, bm)
            M_loc = min(m_cap, inv.shape[0])
            keep = (np.asarray(pos) < M_loc) & own.reshape(n * k)
            keep_all[di * n:(di + 1) * n] |= keep.reshape(n, k)
    return keep_all


def _fd_check(loss_fn, primal, autodiff, coords, eps=1e-4, rtol=2e-2,
              atol=5e-4):
    """Central finite differences at a handful of coordinates."""
    flat = np.asarray(primal, np.float64).ravel()
    for c in coords:
        e = np.zeros_like(flat)
        e[c] = eps
        up = jnp.asarray((flat + e).reshape(primal.shape), primal.dtype)
        dn = jnp.asarray((flat - e).reshape(primal.shape), primal.dtype)
        fd = (float(loss_fn(up)) - float(loss_fn(dn))) / (2 * eps)
        ad = float(np.asarray(autodiff).ravel()[c])
        np.testing.assert_allclose(ad, fd, rtol=rtol, atol=atol,
                                   err_msg=f"coord {c}")


class TestOverflowRegimeGradients:
    """capacity_factor=0.25 => kept_frac < 1: dropped tokens must get
    exactly-zero FFN gradient and surviving gradients must match finite
    differences (fp64 — the package enables x64)."""

    B, S, H, I, E, k, cf = 2, 32, 8, 16, 4, 2, 0.25

    def _inputs64(self):
        return _inputs(self.B, self.S, self.H, self.I, self.E, jnp.float64)

    def _check_single_device(self, fn, keep):
        x, gw, wg, wu, wd = self._inputs64()
        r = _rand((self.B, self.S, self.H), 1.0, 9, jnp.float64)

        def loss_x(x_):
            y, _, _ = fn(x_, gw, wg, wu, wd)
            return (y * r).sum()

        loss_x = jax.jit(loss_x)
        y, _, stats = fn(x, gw, wg, wu, wd)
        assert 0.0 < float(stats[0]) < 1.0, "not in the overflow regime"
        dx_full = jax.jit(jax.grad(loss_x))(x)
        dx = np.asarray(dx_full).reshape(-1, self.H)

        dropped = ~keep.any(axis=1)
        assert dropped.any(), "test shapes must drop at least one token"
        np.testing.assert_array_equal(dx[dropped], 0.0)

        kept_tok = np.flatnonzero(keep.any(axis=1))[:2]
        coords = [t * self.H + j for t in kept_tok for j in (0, 3)]
        _fd_check(loss_x, x, dx_full, coords)

        # expert-weight FD (the router never sees w_up => FD is clean)
        def loss_w(wu_):
            y_, _, _ = fn(x, gw, wg, wu_, wd)
            return (y_ * r).sum()

        loss_w = jax.jit(loss_w)
        _fd_check(loss_w, wu, jax.jit(jax.grad(loss_w))(wu), [0, 7, 101])

    def test_gather_overflow(self):
        fn = lambda x, gw, wg, wu, wd: L.moe_mlp_forward(
            x, gw, wg, wu, wd, top_k=self.k, capacity_factor=self.cf)
        x, gw, *_ = self._inputs64()
        keep = _keep_mask_global(x, gw, self.k, self.E, self.cf)
        self._check_single_device(fn, keep)

    def test_einsum_overflow(self):
        fn = lambda x, gw, wg, wu, wd: L.moe_mlp_forward_einsum(
            x, gw, wg, wu, wd, top_k=self.k, capacity_factor=self.cf,
            groups=1)
        x, gw, *_ = self._inputs64()
        keep = _keep_mask_global(x, gw, self.k, self.E, self.cf)
        self._check_single_device(fn, keep)

    def test_grouped_no_capacity_fd(self):
        """Single-device grouped drops nothing — FD parity only."""
        x, gw, wg, wu, wd = self._inputs64()
        r = _rand((self.B, self.S, self.H), 1.0, 9, jnp.float64)

        def loss_x(x_):
            y, _, _ = L.moe_mlp_forward_grouped(
                x_, gw, wg, wu, wd, top_k=self.k, block_m=8)
            return (y * r).sum()

        loss_x = jax.jit(loss_x)
        _fd_check(loss_x, x, jax.jit(jax.grad(loss_x))(x), [0, 5, 63, 200])

    def test_grouped_sharded_overflow(self):
        """The overflow regression on a dp2 x ep2 x mp2 mesh, cf=0.25
        (kept_frac < 1) — dropped (token, choice) entries must route to
        the zero sentinel row, giving dropped tokens exactly-zero dx and
        finite-difference-correct gradients everywhere else."""
        from jax.sharding import Mesh

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU platform")
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                    ("dp", "ep", "mp"))
        bm = 8
        x, gw, wg, wu, wd = self._inputs64()
        r = _rand((self.B, self.S, self.H), 1.0, 9, jnp.float64)

        def fwd(x_, gw_, wg_, wu_, wd_):
            return L.moe_mlp_forward_grouped_sharded(
                x_, gw_, wg_, wu_, wd_, mesh=mesh, top_k=self.k,
                block_m=bm, capacity_factor=self.cf)

        y, _, stats = jax.jit(fwd)(x, gw, wg, wu, wd)
        assert 0.0 < float(stats[0]) < 1.0, "not in the overflow regime"

        keep = _keep_mask_sharded(x, gw, self.k, self.E, ep=2, dp=2,
                                  bm=bm, cf=self.cf)
        kept_frac = keep.sum() / keep.size
        np.testing.assert_allclose(float(stats[0]), kept_frac, rtol=1e-6)

        def loss_x(x_):
            y_, _, _ = fwd(x_, gw, wg, wu, wd)
            return (y_ * r).sum()

        loss_x_j = jax.jit(loss_x)
        dx = np.asarray(jax.jit(jax.grad(loss_x))(x)).reshape(-1, self.H)
        dropped = ~keep.any(axis=1)
        assert dropped.any(), "test shapes must drop at least one token"
        np.testing.assert_array_equal(dx[dropped], 0.0)

        kept_tok = np.flatnonzero(keep.any(axis=1))[:3]
        coords = [t * self.H + j for t in kept_tok for j in (1, 4)]
        _fd_check(loss_x_j, x, dx.reshape(x.shape), coords)

        def loss_w(wu_):
            y_, _, _ = fwd(x, gw, wg, wu_, wd)
            return (y_ * r).sum()

        _fd_check(jax.jit(loss_w), wu, jax.jit(jax.grad(loss_w))(wu),
                  [0, 7, 101])

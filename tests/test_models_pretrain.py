"""Flagship model + SPMD pipeline + hybrid pretrain-step tests (CPU 8-device
mesh; SURVEY.md §4 parity idiom: parallel vs serial on the same data)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle


def test_llama_train_eager(rng):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    import paddle_tpu.optimizer as opt

    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    ids = paddle.to_tensor(rng.integers(0, 256, (2, 16)))
    labels = paddle.to_tensor(rng.integers(0, 256, (2, 16)))
    o = opt.AdamW(1e-3, parameters=m.parameters())
    losses = []
    for _ in range(3):
        _, loss = m(ids, labels=labels)
        loss.backward()
        o.step()
        o.clear_grad()
        losses.append(float(loss.item()))
    assert losses[-1] < losses[0]


def test_llama_gqa_and_recompute_parity(rng):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    ids = paddle.to_tensor(rng.integers(0, 256, (2, 16)))
    labels = paddle.to_tensor(rng.integers(0, 256, (2, 16)))
    paddle.seed(3)
    m1 = LlamaForCausalLM(LlamaConfig.tiny())             # GQA kv_heads=2
    paddle.seed(3)
    m2 = LlamaForCausalLM(LlamaConfig.tiny(recompute=True))
    l1 = m1(ids, labels=labels)[1]
    l2 = m2(ids, labels=labels)[1]
    np.testing.assert_allclose(float(l1.item()), float(l2.item()), rtol=1e-6)
    l2.backward()
    g = [p.grad for p in m2.parameters() if p.grad is not None]
    assert len(g) > 0


def test_gpt_train_eager(rng):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    import paddle_tpu.optimizer as opt

    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig.tiny())
    ids = paddle.to_tensor(rng.integers(0, 128, (2, 16)))
    labels = paddle.to_tensor(rng.integers(0, 128, (2, 16)))
    o = opt.Adam(1e-3, parameters=m.parameters())
    first = None
    for _ in range(3):
        _, loss = m(ids, labels=labels)
        loss.backward()
        o.step()
        o.clear_grad()
        first = first if first is not None else float(loss.item())
    assert float(loss.item()) < first


def test_pipeline_spmd_parity(rng):
    from paddle_tpu.distributed.pipeline_spmd import pipeline_apply

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "pp"))
    S, M, mb, H = 4, 8, 2, 16
    w = jnp.asarray(rng.standard_normal((S, H, H)).astype(np.float32) * 0.3)
    micro = jnp.asarray(rng.standard_normal((M, mb, H)).astype(np.float32))

    def stage_fn(params, x):
        return jnp.tanh(x @ params)

    def ref(w, m):
        r = m
        for s in range(S):
            r = jnp.tanh(r @ w[s])
        return r

    out = pipeline_apply(mesh, "pp", stage_fn, w, micro)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(w, micro)),
                               rtol=1e-5, atol=1e-6)

    # backward parity, jitted, with sharded inputs
    def loss_pipe(w, m):
        return (pipeline_apply(mesh, "pp", stage_fn, w, m) ** 2).sum()

    wp = jax.device_put(w, NamedSharding(mesh, P("pp")))
    mi = jax.device_put(micro, NamedSharding(mesh, P(None, "dp")))
    val, grad = jax.jit(jax.value_and_grad(loss_pipe))(wp, mi)
    g_ref = jax.grad(lambda w, m: (ref(w, m) ** 2).sum())(w, micro)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def test_pipeline_single_stage_scan(rng):
    from paddle_tpu.distributed.pipeline_spmd import pipeline_apply

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "pp"))
    w = jnp.asarray(rng.standard_normal((1, 8, 8)).astype(np.float32))
    micro = jnp.asarray(rng.standard_normal((3, 2, 8)).astype(np.float32))
    out = pipeline_apply(mesh, "pp", lambda p, x: x @ p, w, micro)
    np.testing.assert_allclose(np.asarray(out), np.asarray(micro @ w[0]),
                               rtol=1e-5)


@pytest.mark.parametrize("pcfg_kw,name", [
    (dict(dp=2, pp=2, mp=2, micro_batches=4, sequence_parallel=True,
          remat=True), "dp2pp2mp2_sp_remat"),
    (dict(dp=8), "dp8"),
    (dict(mp=8, sequence_parallel=True), "mp8_sp"),
    (dict(pp=2, mp=2, micro_batches=4, schedule="interleave",
          virtual_pp=2), "pp2v2_interleave"),
    (dict(dp=2, pp=2, micro_batches=4, schedule="1f1b",
          remat=True), "pp2_1f1b"),
    (dict(pp=2, mp=2, micro_batches=4, schedule="zbh1"), "pp2_zbh1"),
    (dict(dp=2, sep=2, mp=2), "dp2_sep2_mp2_ulysses"),
    (dict(sep=2, mp=2, remat=True), "sep2_mp2_remat"),
    (dict(dp=2, pp=4, micro_batches=8, schedule="zbh1",
          remat=True), "pp4_zbh1_remat"),
    (dict(pp=2, mp=2, micro_batches=4, schedule="zbvpp",
          virtual_pp=2), "pp2v2_zbvpp"),
    (dict(dp=2, pp=2, micro_batches=4, schedule="zbvpp",
          virtual_pp=2, remat=True), "dp2pp2v2_zbvpp_remat"),
])
def test_pretrain_hybrid_parity(rng, pcfg_kw, name):
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    ids = rng.integers(0, 256, (8, 16))
    labels = rng.integers(0, 256, (8, 16))

    ser = PretrainStep(cfg, ParallelConfig())
    s = ser.init_state(seed=7)
    si, sl = ser.shard_batch(ids, labels)
    ref_losses = []
    for _ in range(2):
        s, loss = ser.train_step(s, si, sl)
        ref_losses.append(float(loss))
    assert ref_losses[1] < ref_losses[0]

    par = PretrainStep(cfg, ParallelConfig(**pcfg_kw))
    s2 = par.init_state(seed=7)
    pi, pl_ = par.shard_batch(ids, labels)
    par_losses = []
    for _ in range(2):
        s2, loss = par.train_step(s2, pi, pl_)
        par_losses.append(float(loss))
    np.testing.assert_allclose(ref_losses, par_losses, rtol=1e-4)


def test_pretrain_state_sharded():
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    ps = PretrainStep(cfg, ParallelConfig(pp=2, mp=2, dp=2, micro_batches=2))
    state = ps.init_state(seed=0)
    blocks = state["params"]["blocks"]
    qw = blocks["self_attn.q_proj.weight"]
    assert qw.shape[0] == 2 and qw.shape[1] == 2  # [pp, L/pp, ...]
    spec = qw.sharding.spec
    assert spec[0] == "pp" and spec[-1] == "mp"
    ow = blocks["self_attn.o_proj.weight"]
    assert ow.sharding.spec[2] == "mp"
    assert state["m"]["embed"].dtype == jnp.float32


def test_graft_entry():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (2, 64, 2048)
    g.dryrun_multichip(8)


def test_llama_shard_plan(rng):
    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, llama_shard_plan

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    m = LlamaForCausalLM(LlamaConfig.tiny(hidden_size=64, intermediate_size=128))
    llama_shard_plan(m)
    spec = m.llama.layers[0].self_attn.q_proj.weight._data.sharding.spec
    assert tuple(spec) == (None, "mp")
    ids = paddle.to_tensor(rng.integers(0, 256, (2, 8)))
    logits, loss = m(ids, labels=ids)
    assert np.isfinite(float(loss.item()))


def test_zbh1_schedule_structure():
    """The ZBH1 work table must match the zero-bubble paper's H1 layout
    (reference pipeline_zero_bubble.py:62): W split from B, deferred by the
    stage index, filling the slots where plain 1F1B has no weight work."""
    from paddle_tpu.distributed.pipeline_spmd import (num_pipeline_ticks,
                                                      zbh1_schedule)

    S, M = 4, 8
    table = zbh1_schedule(S, M)
    T = num_pipeline_ticks(M, S, schedule="zbh1")
    assert T == 2 * S + M - 1

    for s in range(S):
        units = [u for (ss, t), us in table.items() if ss == s for u in us]
        for kind in "FBW":
            got = sorted(m for k, m in units if k == kind)
            assert got == list(range(M)), f"stage {s} {kind}: {got}"
        # B(b) runs at b + 2S-1-s; its W(b) runs exactly s ticks later
        for b in range(M):
            t_b = b + 2 * S - 1 - s
            t_w = b + 2 * S - 1
            assert ("B", b) in table[(s, t_b)]
            assert ("W", b) in table[(s, t_w)]
        # stage 0 never defers; the last stage defers W by S-1 ticks
    # cooldown fill: in the last S-1 ticks every stage still has W work
    # (the slots 1F1B leaves as pure bubble on non-final stages)
    for t in range(T - (S - 1), T):
        for s in range(S):
            kinds = {k for k, _ in table.get((s, t), set())}
            assert "W" in kinds, f"no W fill at stage {s} tick {t}"


def test_zbh1_grads_match_1f1b(rng):
    """Same loss AND gradients from the split-backward schedule."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.distributed.pipeline_spmd import (pipeline_1f1b_grads,
                                                      pipeline_zbh1_grads)

    S, M, mb, Dm = 4, 6, 2, 8
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("dp", "pp"))
    w = jnp.asarray(rng.standard_normal((S, Dm, Dm)).astype(np.float32)) * 0.3
    head = jnp.asarray(rng.standard_normal((Dm,)).astype(np.float32))
    micro = jnp.asarray(rng.standard_normal((M, mb, Dm)).astype(np.float32))
    lbls = jnp.asarray(rng.standard_normal((M, mb)).astype(np.float32))

    def stage_fn(p, x):
        return jnp.tanh(x @ p)

    def loss_fn(y, lbl, lp):
        return jnp.sum(jnp.square(y @ lp["head"] - lbl))

    args = (mesh, "pp", stage_fn, loss_fn, w, {"head": head}, micro, lbls)
    l1, g1, glp1, dm1 = pipeline_1f1b_grads(*args)
    l2, g2, glp2, dm2 = pipeline_zbh1_grads(*args)

    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(glp1["head"]),
                               np.asarray(glp2["head"]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dm1), np.asarray(dm2),
                               rtol=1e-4, atol=1e-5)


def test_zbvpp_grads_match_direct(rng):
    """ZBVPP (zero-bubble x virtual pipeline, ref pipeline_zero_bubble.py:151)
    must reproduce the direct full-model loss AND gradients, chunk layout
    included (device-major rows in interleave_chunk_order)."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.distributed.pipeline_spmd import (interleave_chunk_order,
                                                      pipeline_zbvpp_grads)

    S, v, M, mb, Dm = 2, 2, 4, 2, 8
    G = S * v
    mesh = Mesh(np.array(jax.devices()[:S]).reshape(1, S), ("dp", "pp"))
    w_global = jnp.asarray(
        rng.standard_normal((G, Dm, Dm)).astype(np.float32)) * 0.3
    head = jnp.asarray(rng.standard_normal((Dm,)).astype(np.float32))
    micro = jnp.asarray(rng.standard_normal((M, mb, Dm)).astype(np.float32))
    lbls = jnp.asarray(rng.standard_normal((M, mb)).astype(np.float32))

    def stage_fn(p, x):
        return jnp.tanh(x @ p)

    def loss_fn(y, lbl, lp):
        return jnp.sum(jnp.square(y @ lp["head"] - lbl))

    # direct reference: sequential chunks in global order, autodiff grads
    def full_loss(w_g, lp, micro_):
        def fwd(x):
            for g in range(G):
                x = stage_fn(w_g[g], x)
            return x
        return sum(loss_fn(fwd(micro_[m]), lbls[m], lp) for m in range(M))

    ref_l, (ref_gw, ref_glp, ref_dm) = jax.value_and_grad(
        full_loss, argnums=(0, 1, 2))(w_global, {"head": head}, micro)

    order = interleave_chunk_order(S, v)
    w_rows = w_global[jnp.asarray(order)]
    l2, g2, glp2, dm2 = pipeline_zbvpp_grads(
        mesh, "pp", stage_fn, loss_fn, w_rows, {"head": head}, micro, lbls,
        virtual=v)

    np.testing.assert_allclose(float(ref_l), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ref_gw)[np.asarray(order)],
                               np.asarray(g2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref_glp["head"]),
                               np.asarray(glp2["head"]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref_dm), np.asarray(dm2),
                               rtol=1e-4, atol=1e-5)


def test_zbvpp_matches_zbh1_single_chunk(rng):
    """v=1 ZBVPP degenerates to the same math as ZBH1 (different tick
    layout, same gradients)."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.distributed.pipeline_spmd import (pipeline_zbh1_grads,
                                                      pipeline_zbvpp_grads)

    S, M, mb, Dm = 4, 6, 2, 8
    mesh = Mesh(np.array(jax.devices()[:S]).reshape(1, S), ("dp", "pp"))
    w = jnp.asarray(rng.standard_normal((S, Dm, Dm)).astype(np.float32)) * 0.3
    head = jnp.asarray(rng.standard_normal((Dm,)).astype(np.float32))
    micro = jnp.asarray(rng.standard_normal((M, mb, Dm)).astype(np.float32))
    lbls = jnp.asarray(rng.standard_normal((M, mb)).astype(np.float32))

    def stage_fn(p, x):
        return jnp.tanh(x @ p)

    def loss_fn(y, lbl, lp):
        return jnp.sum(jnp.square(y @ lp["head"] - lbl))

    args = (mesh, "pp", stage_fn, loss_fn, w, {"head": head}, micro, lbls)
    l1, g1, glp1, dm1 = pipeline_zbh1_grads(*args)
    l2, g2, glp2, dm2 = pipeline_zbvpp_grads(*args, virtual=1)

    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dm1), np.asarray(dm2),
                               rtol=1e-4, atol=1e-5)


def test_zero3_param_sharding_parity(rng):
    """stage-3: params laid over dp; loss matches the unsharded step and
    the placement actually shards over 'dp'."""
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    ids = rng.integers(0, cfg.vocab_size, (4, 16)).astype("int32")
    labels = rng.integers(0, cfg.vocab_size, (4, 16)).astype("int32")

    base = PretrainStep(cfg, ParallelConfig(dp=2))
    s0 = base.init_state(seed=0)
    _, l0 = base.train_step(s0, *base.shard_batch(ids, labels))

    z3 = PretrainStep(cfg, ParallelConfig(dp=2, zero1=True, zero3=True))
    s1 = z3.init_state(seed=0)
    specs = [str(v.sharding.spec) for v in s1["params"]["blocks"].values()]
    assert any("dp" in s for s in specs), specs
    s1, l1 = z3.train_step(s1, *z3.shard_batch(ids, labels))
    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-4)

    # a second step keeps the sharded placement (update preserves specs)
    s1, _ = z3.train_step(s1, *z3.shard_batch(ids, labels))
    one = next(iter(s1["params"]["blocks"].values()))
    assert "dp" in str(one.sharding.spec)


def test_zero3_composes_with_mp(rng):
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    ids = rng.integers(0, cfg.vocab_size, (4, 16)).astype("int32")
    labels = rng.integers(0, cfg.vocab_size, (4, 16)).astype("int32")
    base = PretrainStep(cfg, ParallelConfig(dp=1))
    b0 = base.init_state(seed=0)
    _, l0 = base.train_step(b0, *base.shard_batch(ids, labels))
    z = PretrainStep(cfg, ParallelConfig(dp=2, mp=2, zero3=True))
    s = z.init_state(seed=0)
    s, l1 = z.train_step(s, *z.shard_batch(ids, labels))
    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-4)


def test_remat_policy_dots_parity(rng):
    """remat_policy='dots' changes what backward recomputes, not the math:
    losses must match full-recompute remat bit-for-bit-ish."""
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    ids = rng.integers(0, 256, (4, 16))
    labels = rng.integers(0, 256, (4, 16))

    losses = {}
    for policy in ("full", "dots"):
        ps = PretrainStep(cfg, ParallelConfig(remat=True,
                                              remat_policy=policy))
        s = ps.init_state(seed=3)
        si, sl = ps.shard_batch(ids, labels)
        out = []
        for _ in range(3):
            s, loss = ps.train_step(s, si, sl)
            out.append(float(loss))
        losses[policy] = out
    assert losses["full"][-1] < losses["full"][0]
    np.testing.assert_allclose(losses["full"], losses["dots"], rtol=2e-5)


def test_remat_policy_validation():
    import pytest

    from paddle_tpu.models.pretrain import ParallelConfig

    with pytest.raises(ValueError, match="remat_policy"):
        ParallelConfig(remat=True, remat_policy="nope")
    with pytest.raises(ValueError, match="remat=False"):
        ParallelConfig(remat_policy="dots")  # policy without remat=True


@pytest.fixture(scope="module")
def flash_one_device():
    """Two interpreted-kernel steps on one device: what every mesh case
    below must reproduce."""
    return _flash_steps({})


def _flash_steps(parallel, calls=None, monkeypatch=None):
    from paddle_tpu import flags
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    if calls is not None:
        real = fa._fa_pallas_forward
        monkeypatch.setattr(
            fa, "_fa_pallas_forward",
            lambda q, *a, **kw: calls.append(q.shape) or real(q, *a, **kw))
    cfg = LlamaConfig.tiny(hidden_size=256, max_position_embeddings=128)
    ids = np.random.default_rng(0).integers(0, 250, (4, 128)).astype(np.int32)
    flags.set_flags({"flash_attention_interpret": True})
    try:
        ps = PretrainStep(cfg, ParallelConfig(**parallel))
        state = ps.init_state(seed=0)
        state, l0 = ps.train_step(state, ids, ids)
        state, l1 = ps.train_step(state, ids, ids)
        return float(l0), float(l1)
    finally:
        flags.set_flags({"flash_attention_interpret": False})


@pytest.mark.parametrize("parallel,shard", [
    (dict(dp=2, mp=2), (2, 128, 2, 64)),       # batch 4/dp2, q heads 4/mp2
    (dict(dp=2, sep=2), (2, 128, 2, 64)),      # heads over sep (Ulysses)
], ids=["dp2_mp2", "dp2_sep2"])
def test_flash_kernel_under_mesh_matches_single_device(
        flash_one_device, monkeypatch, parallel, shard):
    """PR 21: GSPMD cannot partition a Mosaic kernel, so on a multi-device
    mesh the flash entry splits the Pallas call by hand (batch over dp,
    heads over sep/mp) in a shard_map.  Interpret mode runs that same
    wrapper on the virtual mesh: the kernel must actually be in the step
    at the per-shard shape, and two steps' losses must match the
    single-device run."""
    calls = []
    losses = _flash_steps(parallel, calls, monkeypatch)
    assert shard in calls and (4, 128, 4, 64) not in calls
    np.testing.assert_allclose(losses, flash_one_device, rtol=1e-4)
    assert flash_one_device[1] < flash_one_device[0]


def test_flash_on_a_chip_mesh_inside_pipeline_stages_is_refused_at_build(
        monkeypatch):
    """pp > 1 with other mesh axes > 1: the hand split is not wired inside
    the pipeline's shard_map, and on a TPU the compiler would refuse the
    kernel mid-compile — PretrainStep says so when it is built.  pp alone
    and pp == 1 build."""
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")
    cfg = LlamaConfig.tiny()
    with pytest.raises(NotImplementedError, match="pipeline stages"):
        PretrainStep(cfg, ParallelConfig(pp=2, mp=2))
    PretrainStep(cfg, ParallelConfig(pp=2))
    PretrainStep(cfg, ParallelConfig(dp=2, mp=2))


def test_flash_under_mesh_names_the_heads_it_cannot_split():
    """2 kv heads do not divide over sep2 x mp2: a sentence at trace time,
    not a shape error from inside the shard_map."""
    with pytest.raises(ValueError, match=r"kv heads \(2\)"):
        _flash_steps(dict(sep=2, mp=2))

"""Guard-cache discipline: pad-to-bucket compilation for dynamic dims,
LRU eviction caps, and recompile telemetry (VERDICT r4 item 4; reference
surface: SOT guard cache + pir DimExpr dynamic shapes)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu import jit as pjit
from paddle_tpu.jit import InputSpec, to_static
from paddle_tpu.utils.cache import LruCache


class TestLruCache:
    def test_eviction_order_and_stats(self):
        evicted = []
        c = LruCache(3, on_evict=lambda k, v: evicted.append(k))
        for i in range(4):
            c[i] = i * 10
        assert len(c) == 3 and evicted == [0]
        assert c.get(1) == 10          # touch 1 -> 2 becomes LRU
        c[4] = 40
        assert evicted == [0, 2]
        s = c.stats()
        assert s["evictions"] == 2 and s["size"] == 3

    def test_callable_capacity(self):
        cap = [2]
        c = LruCache(lambda: cap[0])
        c[1] = c[2] = 1
        cap[0] = 1
        c[3] = 1                        # shrunk live: evicts down to 1
        assert len(c) == 1

    def test_unbounded_when_nonpositive(self):
        c = LruCache(0)
        for i in range(100):
            c[i] = i
        assert len(c) == 100


class TestBucketing:
    def test_50_lengths_compile_at_most_bucket_count(self):
        compiled_before = pjit.cache_stats()["to_static"]["compiles"]
        fn = to_static(lambda x: x * 2 + 1,
                       input_spec=[InputSpec([None, 8], "float32")],
                       bucket="pow2")
        rng = np.random.default_rng(0)
        for n in range(3, 53):          # 50 distinct lengths, 4..64
            x = paddle.to_tensor(
                rng.standard_normal((n, 8)).astype("float32"))
            out = fn(x)
            assert tuple(out.shape) == (n, 8)       # sliced back
            np.testing.assert_allclose(out.numpy(), x.numpy() * 2 + 1,
                                       rtol=1e-6)
        compiles = pjit.cache_stats()["to_static"]["compiles"] \
            - compiled_before
        # lengths 3..52 -> pow2 buckets {4, 8, 16, 32, 64} = 5 programs
        assert compiles <= 5, compiles
        assert len(fn._cache) <= 5

    def test_explicit_bucket_ladder(self):
        fn = to_static(lambda x: x + 1,
                       input_spec=[InputSpec([None], "float32")],
                       bucket=[16, 64])
        for n in (3, 9, 15, 17, 40, 64):
            out = fn(paddle.to_tensor(np.ones(n, "float32")))
            assert tuple(out.shape) == (n,)
        assert len(fn._cache) <= 2
        # above the last rung: exact compile, still correct
        out = fn(paddle.to_tensor(np.ones(70, "float32")))
        assert tuple(out.shape) == (70,)
        assert len(fn._cache) <= 3

    def test_no_bucket_compiles_per_length(self):
        fn = to_static(lambda x: x + 1,
                       input_spec=[InputSpec([None], "float32")])
        for n in (3, 4, 5):
            fn(paddle.to_tensor(np.ones(n, "float32")))
        assert len(fn._cache) == 3      # the unbucketed baseline behavior

    def test_input_exactly_at_bucket_not_truncated(self):
        # regression (r5 review, reworked): input a sits exactly at the
        # bucket (no padding), input b below it.  Outputs are sliced to
        # the TRUE shapes recorded from an unpadded run — so a's output
        # keeps its full 128 rows and b's comes back at b's own length
        # (the old (axis, size)==bucket heuristic could only give both
        # outputs one shared length)
        fn = to_static(lambda a, b: (a * 2, b * 2),
                       input_spec=[InputSpec([None, 4], "float32"),
                                   InputSpec([None, 4], "float32")],
                       bucket=[128])
        a = paddle.to_tensor(np.ones((128, 4), "float32"))
        b = paddle.to_tensor(np.ones((100, 4), "float32"))
        for _ in range(2):          # eager recording call, then the jit run
            oa, ob = fn(a, b)
            assert tuple(oa.shape) == (128, 4)
            assert tuple(ob.shape) == (100, 4)
            np.testing.assert_allclose(oa.numpy(), 2.0)
            np.testing.assert_allclose(ob.numpy(), 2.0)

    def test_bucket_sized_output_axis_not_truncated(self):
        # an output axis that LEGITIMATELY has the
        # bucket's size at a padded axis position (here: a fixed [128, 8]
        # projection output while the input's axis 0 pads 100 -> 128) must
        # not be cut down to the batch's true length
        fn = to_static(
            lambda x: (x * 3, paddle.ones([128, 8]) * x.sum(axis=0)),
            input_spec=[InputSpec([None, 8], "float32")],
            bucket=[128])
        x = paddle.to_tensor(np.ones((100, 8), "float32"))
        for _ in range(2):          # recording call, then the jit run
            ox, proj = fn(x)
            assert tuple(ox.shape) == (100, 8)
            assert tuple(proj.shape) == (128, 8)   # NOT truncated to 100
            np.testing.assert_allclose(ox.numpy(), 3.0)
            np.testing.assert_allclose(proj.numpy(), 100.0)

    def test_bucket_kwarg_tensor_pads_right_axis(self):
        # input_spec is aligned with the call STRUCTURE (args then sorted
        # kwargs), so a tensor passed by keyword still pads its own axes
        fn = to_static(lambda a, b=None: (a + 1, b.sum(axis=0)),
                       input_spec=[InputSpec([None, 4], "float32"),
                                   InputSpec([None, 2], "float32")],
                       bucket=[8])
        a = paddle.to_tensor(np.ones((5, 4), "float32"))
        b = paddle.to_tensor(np.ones((7, 2), "float32"))
        for _ in range(2):
            oa, ob = fn(a, b=b)
            assert tuple(oa.shape) == (5, 4)
            np.testing.assert_allclose(ob.numpy(), 7.0)  # pad rows are 0

    def test_bucket_spec_structure_mismatch_raises(self):
        fn = to_static(lambda a: a * 2,
                       input_spec=[InputSpec([None], "float32"),
                                   InputSpec([None], "float32")],
                       bucket=[8])
        with pytest.raises(ValueError):
            fn(paddle.to_tensor(np.ones(3, "float32")))  # 2 specs, 1 arg
        fn2 = to_static(lambda a: a[0] * 2,
                        input_spec=[InputSpec([None], "float32")],
                        bucket=[8])
        with pytest.raises(ValueError):  # spec says tensor, call passes list
            fn2([paddle.to_tensor(np.ones(3, "float32")),
                 paddle.to_tensor(np.ones(3, "float32"))])

    def test_grad_flows_through_padded_program(self):
        model = paddle.nn.Linear(8, 4)
        fwd = to_static(model, input_spec=[InputSpec([None, 8], "float32")],
                        bucket="pow2")
        x = paddle.to_tensor(np.ones((5, 8), "float32"))
        out = model(x)
        loss = out.sum()
        loss.backward()
        g = model.weight.grad
        assert g is not None
        # padded rows are zeros: the weight grad equals the unpadded one
        np.testing.assert_allclose(g.numpy(),
                                   np.ones((8, 4), "float32") * 5, rtol=1e-5)


class TestGuardCacheLru:
    def test_static_cache_capped(self):
        flags.set_flags({"FLAGS_to_static_cache_size": 4})
        try:
            before = pjit.cache_stats()["to_static"]["evictions"]
            fn = to_static(lambda x: x * 2)
            for n in range(1, 11):      # 10 distinct shapes, cap 4
                fn(paddle.to_tensor(np.ones(n, "float32")))
            assert len(fn._cache) <= 4
            assert pjit.cache_stats()["to_static"]["evictions"] - before >= 6
        finally:
            flags.set_flags({"FLAGS_to_static_cache_size": 64})

    def test_evicted_entry_recompiles_and_still_works(self):
        flags.set_flags({"FLAGS_to_static_cache_size": 2})
        try:
            fn = to_static(lambda x: x + 1)
            xs = [paddle.to_tensor(np.ones(n, "float32")) for n in (1, 2, 3)]
            for x in xs * 2:            # cycle: constant thrash, still right
                out = fn(x)
                np.testing.assert_allclose(out.numpy(), x.numpy() + 1)
            assert len(fn._cache) <= 2
        finally:
            flags.set_flags({"FLAGS_to_static_cache_size": 64})


class TestDispatchCacheLru:
    def test_eager_jit_cache_capped(self):
        from paddle_tpu.core import autograd as eng

        flags.set_flags({"FLAGS_eager_jit_cache_size": 2})
        try:
            eng._jit_cache.clear()
            x = paddle.to_tensor(np.ones(4, "float32"))
            for op in (paddle.exp, paddle.sin, paddle.cos, paddle.tanh):
                op(x)
            assert len(eng._jit_cache) <= 2
            stats = eng.dispatch_cache_stats()
            assert stats["jit"]["evictions"] >= 2
            # evicted op still computes correctly (recompiles)
            np.testing.assert_allclose(paddle.exp(x).numpy(),
                                       np.exp(np.ones(4, "float32")),
                                       rtol=1e-6)
        finally:
            flags.set_flags({"FLAGS_eager_jit_cache_size": 4096})

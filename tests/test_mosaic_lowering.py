"""Cross-lower every Pallas kernel to REAL TPU Mosaic on the CPU host.

CPU tests exercise the kernels in interpret mode, which skips Mosaic's
MLIR lowering entirely — so a kernel can be green on CPU yet fail to
compile on the chip (round 4 lost four ladder configs to exactly that: an
int64 literal from a Python-int divisor sent Mosaic's convert_element_type
lowering into infinite recursion).  ``jax.export`` with
``platforms=['tpu']`` runs the full Mosaic lowering pipeline without TPU
hardware, making chip-only lowering bugs visible in the CPU suite.

Reference analog: the CUDA build compiles flash_attn kernels at build time
(paddle/phi/kernels/gpu/flash_attn_kernel.cu) so lowering failures surface
before runtime; this is the TPU equivalent.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.paged_attention import pool_of_heads


def _export_tpu(fn, *args):
    """Lower ``fn`` for the TPU platform (no hardware needed)."""
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def _rand(shape, dtype=jnp.bfloat16, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


class TestFlashAttentionMosaic:
    B, S, H, D = 1, 256, 4, 128

    def _qkv(self, hkv=None):
        q = _rand((self.B, self.S, self.H, self.D))
        k = _rand((self.B, self.S, hkv or self.H, self.D), seed=1)
        v = _rand((self.B, self.S, hkv or self.H, self.D), seed=2)
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward(self, causal):
        from paddle_tpu.kernels.flash_attention import _fa_pallas_forward

        q, k, v = self._qkv()
        _export_tpu(lambda a, b, c: _fa_pallas_forward(
            a, b, c, causal, None, None, None, (128, 128), "tpu")[0],
            q, k, v)

    def test_forward_gqa(self):
        from paddle_tpu.kernels.flash_attention import _fa_pallas_forward

        q, k, v = self._qkv(hkv=2)
        _export_tpu(lambda a, b, c: _fa_pallas_forward(
            a, b, c, True, None, None, None, (128, 128), "tpu")[0],
            q, k, v)

    def test_forward_mask(self):
        from paddle_tpu.kernels.flash_attention import _fa_pallas_forward

        q, k, v = self._qkv()
        mask = jnp.zeros((self.B, 1, self.S, self.S), jnp.float32)
        _export_tpu(lambda a, b, c, m: _fa_pallas_forward(
            a, b, c, False, m, None, None, (128, 128), "tpu")[0],
            q, k, v, mask)

    def test_forward_segments(self):
        from paddle_tpu.kernels.flash_attention import _fa_pallas_forward

        q, k, v = self._qkv()
        seg = jnp.zeros((self.B, self.S), jnp.int32)
        _export_tpu(lambda a, b, c, s: _fa_pallas_forward(
            a, b, c, False, None, s, s, (128, 128), "tpu")[0],
            q, k, v, seg)

    def test_forward_dropout(self):
        from paddle_tpu.kernels.flash_attention import _fa_pallas_forward

        q, k, v = self._qkv()
        seed = jnp.zeros((1, 1), jnp.float32)
        _export_tpu(lambda a, b, c, s: _fa_pallas_forward(
            a, b, c, True, None, None, None, (128, 128), "tpu",
            0.1, s)[0], q, k, v, seed)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward(self, causal, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa

        monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")
        q, k, v = self._qkv()

        def loss(a, b, c):
            return fa._flash_attention_arrays(
                a, b, c, causal).astype(jnp.float32).sum()

        _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    def test_backward_dropout(self, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa

        monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")
        q, k, v = self._qkv()
        seed = jnp.zeros((1, 1), jnp.float32)

        def loss(a, b, c, s):
            return fa._flash_attention_arrays(
                a, b, c, True, drop_p=0.1,
                seed=s).astype(jnp.float32).sum()

        _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v, seed)


class TestPagedAttentionMosaic:
    b, qh, kvh, d = 2, 8, 4, 128
    n_pages, page_size, max_pages = 16, 32, 8

    def _cache(self):
        k_cache = _rand((self.kvh, self.n_pages, self.page_size, self.d),
                        seed=1)
        v_cache = _rand((self.kvh, self.n_pages, self.page_size, self.d),
                        seed=2)
        bt = jnp.zeros((self.b, self.max_pages), jnp.int32)
        cl = jnp.full((self.b,), 40, jnp.int32)
        return pool_of_heads(k_cache, v_cache), bt, cl

    def test_decode_kernel(self):
        from paddle_tpu.kernels.paged_attention import \
            _pallas_ragged_paged_attention

        q = _rand((self.b, 1, self.qh, self.d))
        kv, bt, cl = self._cache()
        _export_tpu(
            lambda *a: _pallas_ragged_paged_attention(
                *a, None, None, None, False)[0],
            q, kv, bt, cl)

    def test_mixed_mode_kernel(self):
        """Prefill chunk + fresh-KV causal fold, the ragged mixed form."""
        from paddle_tpu.kernels.paged_attention import \
            _pallas_ragged_paged_attention

        T = 16
        q = _rand((self.b, T, self.qh, self.d))
        kv, bt, cl = self._cache()
        ql = jnp.asarray([T, 3], jnp.int32)
        kn = _rand((self.b, T, self.kvh, self.d), seed=3)
        vn = _rand((self.b, T, self.kvh, self.d), seed=4)
        _export_tpu(
            lambda q_, kv_, bt_, cl_, ql_, kn_, vn_:
                _pallas_ragged_paged_attention(
                    q_, kv_, bt_, cl_, ql_, kn_, vn_, False)[0],
            q, kv, bt, cl, ql, kn, vn)

    def _int8_cache(self):
        """int8 KV pool + per-(kv-head, page) fp32 scales (ISSUE 13)."""
        rng = np.random.default_rng(7)
        kc = jnp.asarray(rng.integers(
            -127, 128, (self.kvh, self.n_pages, self.page_size, self.d)),
            jnp.int8)
        vc = jnp.asarray(rng.integers(
            -127, 128, (self.kvh, self.n_pages, self.page_size, self.d)),
            jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02,
                                     (self.kvh, self.n_pages)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02,
                                     (self.kvh, self.n_pages)), jnp.float32)
        bt = jnp.zeros((self.b, self.max_pages), jnp.int32)
        cl = jnp.full((self.b,), 40, jnp.int32)
        return pool_of_heads(kc, vc), ks, vs, bt, cl

    @pytest.mark.parametrize("T,ql", [(1, (1, 1)),     # pure decode
                                      (4, (4, 1)),     # T=K spec verify
                                      (16, (16, 3))])  # prefill chunk
    def test_int8_kernel_all_serving_modes(self, T, ql):
        """ISSUE 13: cross-lower the int8 ragged kernel in every serving
        program shape — decode T=1, the T=K verify bucket and a ragged
        prefill chunk — so the chip-capture queue isn't blocked on a
        lowering surprise (the SMEM scale load at a dynamic page id is
        exactly the construct interpret mode cannot exercise)."""
        from paddle_tpu.kernels.paged_attention import \
            _pallas_ragged_paged_attention

        kv, ks, vs, bt, cl = self._int8_cache()
        q = _rand((self.b, T, self.qh, self.d), jnp.float32)
        qlv = jnp.asarray(ql, jnp.int32)
        kn = _rand((self.b, T, self.kvh, self.d), jnp.float32, seed=3)
        vn = _rand((self.b, T, self.kvh, self.d), jnp.float32, seed=4)
        _export_tpu(
            lambda q_, kv_, bt_, cl_, ql_, kn_, vn_, ks_, vs_:
                _pallas_ragged_paged_attention(
                    q_, kv_, bt_, cl_, ql_, kn_, vn_, False,
                    ks_, vs_)[0],
            q, kv, bt, cl, qlv, kn, vn, ks, vs)

    def test_int8_quantized_commit_lowering(self):
        """The page-RMW quantized commit must also reach the chip: lower
        the all-layer gather->dequant->insert->requant->scatter program
        over an int8 pool at the decode shape."""
        from paddle_tpu.kernels.paged_attention import \
            write_kv_pages_all_layers_quantized

        L, B, T = 2, self.b, 1
        rng = np.random.default_rng(9)
        kv = jnp.asarray(rng.integers(
            -127, 128,
            (L, self.n_pages, 2, self.kvh, self.page_size, self.d)),
            jnp.int8)
        ks = jnp.ones((L, self.kvh, self.n_pages), jnp.float32)
        vs = jnp.ones((L, self.kvh, self.n_pages), jnp.float32)
        k_all = _rand((L, B * T, self.kvh, self.d), jnp.float32)
        v_all = _rand((L, B * T, self.kvh, self.d), jnp.float32, seed=5)
        pos = jnp.asarray([40, 33], jnp.int32)
        qlv = jnp.ones((B,), jnp.int32)
        bt = jnp.zeros((B, self.max_pages), jnp.int32)
        _export_tpu(
            lambda *a: write_kv_pages_all_layers_quantized(
                *a, self.max_pages * self.page_size),
            kv, ks, vs, k_all, v_all, pos, qlv, bt)

    @pytest.mark.parametrize("K", [4, 8])
    def test_spec_verify_bucket_kernel(self, K):
        """ISSUE 9: the speculative verify step runs the mixed-mode
        kernel at the NEW T=K bucket (K in {4, 8}, ragged q_lens =
        1 + draft_len per row) — cross-lower it so a chip-only Mosaic
        failure can't hide behind CPU interpret mode.  T*group here is
        not a sublane multiple, exercising the q-row pad path."""
        from paddle_tpu.kernels.paged_attention import \
            _pallas_ragged_paged_attention

        q = _rand((self.b, K, self.qh, self.d))
        kv, bt, cl = self._cache()
        ql = jnp.asarray([K, 1], jnp.int32)   # full draft vs no-draft row
        kn = _rand((self.b, K, self.kvh, self.d), seed=3)
        vn = _rand((self.b, K, self.kvh, self.d), seed=4)
        _export_tpu(
            lambda q_, kv_, bt_, cl_, ql_, kn_, vn_:
                _pallas_ragged_paged_attention(
                    q_, kv_, bt_, cl_, ql_, kn_, vn_, False)[0],
            q, kv, bt, cl, ql, kn, vn)


class TestTensorParallelMosaic:
    """ISSUE 18: cross-lower the kv-head-sharded ragged kernel under
    shard_map in every serving program shape.  The tensor-parallel step
    runs the SAME Pallas kernel on a [kvh/tp, ...] shard-local pool with
    q sliced to the shard's query heads — Mosaic sees different block
    shapes than the tp=1 lowering, and the collective pair
    (axis_index/all_gather) must survive the TPU lowering pipeline, so a
    chip-only failure can't hide behind CPU interpret mode."""

    b, qh, kvh, d = 2, 8, 4, 128
    n_pages, page_size, max_pages = 16, 32, 8
    tp = 2

    def _mesh(self):
        return jax.sharding.Mesh(
            np.asarray(jax.devices()[:self.tp]), ("mp",))

    def _cache(self):
        kc = _rand((self.kvh, self.n_pages, self.page_size, self.d),
                   seed=1)
        vc = _rand((self.kvh, self.n_pages, self.page_size, self.d),
                   seed=2)
        bt = jnp.zeros((self.b, self.max_pages), jnp.int32)
        cl = jnp.full((self.b,), 40, jnp.int32)
        return pool_of_heads(kc, vc), bt, cl

    def _shard_export(self, T, ql, int8=False):
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.kernels.paged_attention import \
            _pallas_ragged_paged_attention

        mesh = self._mesh()
        qh_l = self.qh // self.tp
        kvh_l = self.kvh // self.tp
        dt = jnp.float32 if int8 else jnp.bfloat16
        q = _rand((self.b, T, self.qh, self.d), dt)
        if int8:
            rng = np.random.default_rng(7)
            shape = (self.kvh, self.n_pages, self.page_size, self.d)
            kv = pool_of_heads(
                jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                jnp.asarray(rng.integers(-127, 128, shape), jnp.int8))
            ks = jnp.asarray(rng.uniform(0.005, 0.02,
                                         (self.kvh, self.n_pages)),
                             jnp.float32)
            vs = jnp.asarray(ks)
            bt = jnp.zeros((self.b, self.max_pages), jnp.int32)
            cl = jnp.full((self.b,), 40, jnp.int32)
        else:
            kv, bt, cl = self._cache()
            ks = vs = None
        decode = T == 1 and ql is None
        qlv = None if decode else jnp.asarray(ql, jnp.int32)
        kn = None if decode else _rand((self.b, T, self.kvh, self.d),
                                       dt, seed=3)
        vn = None if decode else _rand((self.b, T, self.kvh, self.d),
                                       dt, seed=4)

        def body(q_, kv_, bt_, cl_, ql_=None, kn_=None, vn_=None,
                 ks_=None, vs_=None):
            # mirror of generation._forward_tokens' tp layer body: slice
            # q (and fresh KV) to this shard's heads, run the kernel on
            # the shard-local pool, gather heads back
            shard = jax.lax.axis_index("mp")
            q_s = jax.lax.dynamic_slice_in_dim(
                q_, shard * qh_l, qh_l, axis=2)
            if kn_ is not None:
                kn_ = jax.lax.dynamic_slice_in_dim(
                    kn_, shard * kvh_l, kvh_l, axis=2)
                vn_ = jax.lax.dynamic_slice_in_dim(
                    vn_, shard * kvh_l, kvh_l, axis=2)
            attn = _pallas_ragged_paged_attention(
                q_s, kv_, bt_, cl_, ql_, kn_, vn_, False,
                ks_, vs_)[0]
            return jax.lax.all_gather(attn, "mp", axis=2, tiled=True)

        # the pool [pages, K|V, kv_heads, ...] and its scale rows
        # [kv_heads, pages] are sharded where each counts heads
        rep, sh, pool = P(), P("mp"), P(None, None, "mp")
        args = [q, kv, bt, cl]
        specs = [rep, pool, rep, rep]
        if not decode:
            args += [qlv, kn, vn]
            specs += [rep, rep, rep]
        if int8:
            if decode:
                args += [None, None, None]
                specs += [rep, rep, rep]
            args += [ks, vs]
            specs += [sh, sh]
        # check_vma=False as at the engine's own call site (_tp_jit)
        fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                           out_specs=rep, check_vma=False)
        _export_tpu(fn, *args)

    def test_tp_decode_kernel(self):
        self._shard_export(T=1, ql=None)

    def test_tp_spec_verify_kernel(self):
        self._shard_export(T=4, ql=(4, 1))

    def test_tp_prefill_chunk_kernel(self):
        self._shard_export(T=16, ql=(16, 3))

    def test_tp_int8_kernel(self):
        self._shard_export(T=4, ql=(4, 1), int8=True)



class TestLatentIndexMosaic:
    """The learned sparse attention's three kernels (PR 39) through the
    Mosaic pipeline at small shapes of the published tiling (pages of 16,
    index keys and the rope pair 128 wide); ``tests/test_chip_compile.py``
    compiles them at the cell's sizes."""
    B, T, IH, D, H, RANK, ROPE, PAGE, TABLE, P = 2, 16, 8, 128, 8, 128, 64, \
        16, 6, 16

    def _common(self):
        i32 = jnp.int32
        bt = jnp.arange(self.B * self.TABLE, dtype=i32).reshape(
            self.B, self.TABLE) % self.P
        return bt, jnp.asarray([40, 7], i32), jnp.asarray([16, 1], i32)

    def test_scores_kernel(self):
        from paddle_tpu.kernels.latent_index import \
            _pallas_latent_index_scores
        bt, ctx, ql = self._common()
        _export_tpu(
            lambda q, w, k: _pallas_latent_index_scores(
                q, w, k, bt, ctx, ql, interpret=False, layer=jnp.int32(1)),
            _rand((self.B, self.T, self.IH, self.D)),
            _rand((self.B, self.T, self.IH), jnp.float32, 1),
            _rand((2, self.P, self.PAGE, self.D), seed=2))

    def test_select_kernel(self):
        from paddle_tpu.kernels.latent_index import \
            _pallas_latent_index_select
        _, ctx, ql = self._common()
        n = self.TABLE * self.PAGE + self.T
        k = jnp.minimum(ctx[:, None] + jnp.arange(self.T)[None] + 1, 8)
        _export_tpu(
            lambda s: _pallas_latent_index_select(
                s, k, ql, ctx, interpret=False, n_new=self.T),
            _rand((self.B, self.T, n), jnp.float32))

    def test_sparse_latent_kernel(self):
        from paddle_tpu.kernels.paged_attention import \
            _pallas_ragged_paged_attention_latent
        bt, ctx, ql = self._common()
        n = self.TABLE * self.PAGE + self.T
        sel = jnp.ones((self.B, self.T, n), bool)
        _export_tpu(
            lambda qc, qr, c, r, cn, rn:
            _pallas_ragged_paged_attention_latent(
                qc, qr, c, r, bt, ctx, ql, cn, rn, interpret=False,
                scale=0.1352, layer=jnp.int32(0), selected=sel),
            _rand((self.B, self.T, self.H, self.RANK)),
            _rand((self.B, self.T, self.H, self.ROPE), seed=1),
            _rand((2, self.P, self.PAGE, self.RANK), seed=2),
            _rand((2, self.P, self.PAGE // 2, 2 * self.ROPE), seed=3),
            _rand((self.B, self.T, self.RANK), seed=4),
            _rand((self.B, self.T, self.ROPE), seed=5))


class TestWeightOnlyMosaic:
    def test_w8a16(self):
        from paddle_tpu.kernels.weight_only import _wo_core

        m, k, n = 256, 512, 256
        x = _rand((m, k))
        wq = jnp.zeros((k, n), jnp.int8)
        scale = jnp.ones((n,), jnp.float32)
        _export_tpu(lambda a, w, s: _wo_core(
            a, w, s, False, k, (256, 256, 512), jnp.bfloat16, False, n),
            x, wq, scale)


class TestEndToEndMosaic:
    """Cross-lower the compiled train and decode steps at flagship
    geometry (2 layers, per-layer kernel shapes of a 1-2B Llama), so a
    lowering failure that only the TPU target shows is caught on the
    CPU."""

    def _llama_step(self, **extra):
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=extra.pop("hidden_size", 2048),
            intermediate_size=extra.pop("intermediate_size", 5504),
            num_hidden_layers=2, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16", **extra)
        ps = PretrainStep(
            cfg, ParallelConfig(remat=True, loss_chunks=16,
                                m_dtype="bfloat16"))
        state = ps.init_state(seed=0)
        ids = np.zeros((4, 2048), np.int32)

        def step(state, ids, labels):
            loss, grads = jax.value_and_grad(
                lambda p: ps._forward_loss(p, ids, labels))(state["params"])
            return ps._update(state, grads), loss

        return step, (state, ids, ids)

    def test_flagship_train_step(self, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa

        monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")
        step, args = self._llama_step()
        _export_tpu(step, *args)

    def test_moe_train_step(self, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa

        monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")
        step, args = self._llama_step(hidden_size=1024,
                                      intermediate_size=2816,
                                      moe_num_experts=8, moe_top_k=2)
        _export_tpu(step, *args)

    def test_moe_train_step_einsum_dispatch(self, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa

        monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")
        step, args = self._llama_step(hidden_size=1024,
                                      intermediate_size=2816,
                                      moe_num_experts=8, moe_top_k=2,
                                      moe_dispatch="einsum")
        _export_tpu(step, *args)


class TestPrimitivesMosaic:
    def test_matmul(self):
        from paddle_tpu.kernels.primitives import matmul_kernel

        f = matmul_kernel(block_m=128, block_n=128, block_k=128)
        x, y = _rand((256, 256)), _rand((256, 256), seed=1)
        _export_tpu(f, x, y)

    def test_elementwise(self):
        from paddle_tpu.kernels.primitives import elementwise_kernel

        f = elementwise_kernel(lambda x: jnp.maximum(x, 0) * 2.0)
        _export_tpu(f, _rand((8, 1024), jnp.float32))

    def test_reduce(self):
        from paddle_tpu.kernels.primitives import reduce_kernel

        f = reduce_kernel(jnp.add, 0.0)
        _export_tpu(f, _rand((256, 512), jnp.float32))

"""Compile the main path's kernels for a DESCRIBED v5e at llama2_7b widths.

The TPU's compiler is installed in the sandbox and compiles for a chip that
is described, not attached (on-chip-measurement guide, section 2, step 3):
it refuses what interpret mode and ``jax.export`` (tests/
test_mosaic_lowering.py) both let through (a one-row slice of a tiled HBM
operand, an int8 scale plane larger than SMEM).  Nothing runs, so these say
nothing about results or times; ``chip_smoke.py`` is the chip run.

This is the only file that describes the chip.  The topology is described
inside a module-scoped fixture — never at import — so every xdist worker
collects the same tests and only the worker given this file loads the TPU
library.  Keep all such tests in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import paged_attention_latent as pal
from paddle_tpu.kernels import paged_geometry as pg
from paddle_tpu.kernels import paged_pool_writes as pw

# LlamaConfig.llama2_7b() widths + the serving launcher's geometry
QH = KVH = 32
D = 128
HIDDEN, INTER = 4096, 11008
BATCH, PAGE, MAX_LEN = 8, 16, 1024
W = MAX_LEN // PAGE
N_PAGES = BATCH * W
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # such a compile is written to the persistent cache but cannot be read
    # back without a chip: turn the cache off around this module
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


class _OnTpu:
    """``jax`` as the kernels' entry points see it on a chip."""
    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def default_backend():
        return "tpu"


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _paged_shapes(T, cache_dtype=BF16, page=PAGE, n_pages=N_PAGES):
    i32 = jnp.int32
    cache = ((n_pages, 2, KVH, page, D), cache_dtype)
    return [((BATCH, T, QH, D), BF16), cache,
            ((BATCH, MAX_LEN // page), i32), ((BATCH,), i32),
            ((BATCH,), i32), ((BATCH, T, KVH, D), BF16),
            ((BATCH, T, KVH, D), BF16)]


@pytest.mark.parametrize("T", [1, 64, 8],
                         ids=["decode", "prefill_chunk", "spec_verify"])
def test_paged_attention_compiles(one_chip, T):
    _compile(one_chip,
             lambda q, kv, bt, cl, ql, kn, vn:
             pa._pallas_ragged_paged_attention(q, kv, bt, cl, ql, kn, vn,
                                               False),
             *_paged_shapes(T))


@pytest.mark.parametrize("T", [1, 64], ids=["decode", "prefill_chunk"])
def test_paged_attention_int8_compiles(one_chip, T):
    """PR 21 settled this by repair: the per-(kv-head, page) scales ride
    the scalar-prefetch channel (as plain SMEM operands of a scalar-
    prefetch grid Mosaic refused them: 'failed to legalize func.func')."""
    page, n_pages = 32, BATCH * (MAX_LEN // 32)
    scale = ((KVH, n_pages), jnp.float32)
    _compile(one_chip,
             lambda q, kv, bt, cl, ql, kn, vn, ks, vs:
             pa._pallas_ragged_paged_attention(q, kv, bt, cl, ql, kn, vn,
                                               False, k_scale=ks, v_scale=vs),
             *_paged_shapes(T, jnp.int8, page, n_pages), scale, scale)


V5E_SMEM = 1 << 20


@pytest.mark.parametrize("kvh,fits,refused", [(32, 3968, 4096),
                                              (8, 16256, 16384)])
def test_paged_int8_scale_planes_bounded_by_smem(one_chip, kvh, fits,
                                                 refused):
    """Scalar-prefetched scales live in SMEM (1 MiB on v5e).  The bound
    was bisected with this compile (PR 21): the geometry rule and the
    compiler agree on the last pool that fits and on the next 128-page
    step.  So int8 KV is brought up for pools of kv_heads x num_pages up
    to about 127 Ki (3968 pages at 32 heads) and no larger."""
    def rule(n):
        return pg.kernel_geometry_error(
            32, D, quantized=True, kv_heads=kvh, num_pages=n,
            table_shape=(BATCH, MAX_LEN // 32), smem_bytes=V5E_SMEM)

    def compile_pool(n):
        i32, cache = jnp.int32, ((n, 2, kvh, 32, D), jnp.int8)
        new, scale = ((BATCH, 1, kvh, D), BF16), ((kvh, n), jnp.float32)
        _compile(one_chip,
                 lambda q, kv, bt, cl, ql, kn, vn, ks, vs:
                 pa._pallas_ragged_paged_attention(
                     q, kv, bt, cl, ql, kn, vn, False, k_scale=ks,
                     v_scale=vs),
                 ((BATCH, 1, kvh, D), BF16), cache,
                 ((BATCH, MAX_LEN // 32), i32), ((BATCH,), i32),
                 ((BATCH,), i32), new, new, scale, scale)

    assert rule(fits) is None
    compile_pool(fits)
    why = rule(refused)
    assert why and "SMEM" in why
    with pytest.raises(Exception, match="smem"):
        compile_pool(refused)
    # with no chip attached and none described, the rule is the compiler's
    assert pg.kernel_geometry_error(32, D, quantized=True, kv_heads=kvh,
                                    num_pages=refused) is None


def test_flash_fwd_bwd_compiles(one_chip):
    blocks = (512, 512)

    def fwd_bwd(q, k, v, g):
        out, lse = fa._fa_pallas_forward(q, k, v, True, None, None, None,
                                         blocks, "tpu")
        return fa._fa_pallas_backward(q, k, v, jnp.swapaxes(out, 1, 2), lse,
                                      g, True, None, None, None, blocks,
                                      "tpu")

    qkv = ((1, 2048, QH, D), BF16)
    compiled = _compile(one_chip, fwd_bwd, qkv, qkv, qkv, qkv)
    assert compiled.as_text().count("tpu_custom_call") >= 3   # fwd, dq, dkv


M, E, BM = 8192, 8, 512


def test_gmm_compiles(one_chip):
    _compile(one_chip,
             lambda l, r, t: gm.gmm(l, r, t, bm=BM, interpret=False),
             ((M, HIDDEN), BF16), ((E, HIDDEN, INTER), BF16),
             ((M // BM,), jnp.int32))


def test_tgmm_compiles(one_chip):
    _compile(one_chip,
             lambda l, r, t: gm.tgmm(l, r, t, E, bm=BM, interpret=False),
             ((M, HIDDEN), BF16), ((M, INTER), BF16),
             ((M // BM,), jnp.int32))


# ---- command-a-plus-05-2026 as one chip of eight holds it (PR 27) ----
# 128 query heads over 8 KV heads x T = 64 is a 1,024-row query block; the
# cell's engine: 16 slots, 12,416 positions in pages of 16, a pool of 12,416
CA_QH, CA_KVH, CA_B, CA_LEN, CA_PAGES = 128, 8, 16, 12416, 12416


_PAGED_CALL = re.compile(
    r"%(ragged_paged_attention\w*)[.\d]* = \((\w+\[[\d,]+\])\{[^}]*\}, "
    r"(\w+\[[\d,]+\])\{[^}]*\}\) custom-call\(.*"
    r"operand_layout_constraints=\{(\w+\[[\d,]+\])")


def _paged_call(compiled):
    """What the benchmark recognises the kernel's call by (``chipbench/
    kernels/paged_attention.py::match``): (name, output, log-sum-exp,
    first operand) of the one custom call, as the compiled program's text
    has them."""
    calls = _PAGED_CALL.findall(compiled.as_text())
    assert len(calls) == 1, calls
    return calls[0]


def _compile_engine_call(one_chip, slots, qh, kvh, T, table, n_pages, window,
                         layers=4):
    """The kernel as an engine's step calls it: the step's own K/V rows,
    the WHOLE bf16 pool ``[layers, pages, 2, kv_heads, page, d]`` read at a
    traced layer, a table ``[slots, table]``, with the VMEM limit the code
    sets from these shapes."""
    assert pg.kernel_geometry_error(PAGE, D, kv_heads=kvh,
                                    num_pages=n_pages,
                                    table_shape=(slots, table)) is None
    i32 = jnp.int32
    return _compile(
        one_chip,
        lambda q, kv, bt, cl, ql, kn, vn, ly: pa._pallas_ragged_paged_attention(
            q, kv, bt, cl, ql, kn, vn, interpret=False, window=window,
            layer=ly),
        ((slots, T, qh, D), BF16), ((layers, n_pages, 2, kvh, PAGE, D), BF16),
        ((slots, table), i32), ((slots,), i32), ((slots,), i32),
        ((slots, T, kvh, D), BF16), ((slots, T, kvh, D), BF16), ((), i32))


def _pool_shaped_copies(text, pool):
    """The ``copy`` operations of a compiled program whose result has the
    pool's shape: what a traced window would show as a pool-shaped copy."""
    shape = f"bf16[{','.join(map(str, pool.shape))}]"
    return re.findall(r"= " + re.escape(shape) + r"\{[^}]*\} copy\(", text)


@pytest.mark.parametrize("kvh", [4, 8])
def test_the_commit_updates_the_pool_in_place(one_chip, kvh):
    """``write_kv_pages_all_layers`` alone, the pool donated: the loop's
    window is a whole page with ONE dynamic index, which XLA's layout
    assignment leaves in the pool's own layout."""
    i32 = jnp.int32
    pool = jax.ShapeDtypeStruct((4, 2048, 2, kvh, PAGE, D), BF16,
                                sharding=one_chip)
    new = jax.ShapeDtypeStruct((4, 512, kvh, D), BF16, sharding=one_chip)
    slots = jax.ShapeDtypeStruct((512,), i32, sharding=one_chip)
    compiled = jax.jit(pw.write_kv_pages_all_layers, donate_argnums=(0,)) \
        .lower(pool, new, new, slots).compile()
    assert not _pool_shaped_copies(compiled.as_text(), pool)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * 2048 * 2 * kvh * PAGE * D * 2
    assert mem.temp_size_in_bytes < 16 << 20


def _vmem_limit_of(compiled):
    """The VMEM a compiled program's one paged call is given, bytes: the
    size of the scoped memory in the custom call's ``backend_config``
    (``vmem_limit_bytes`` as the compiler took it)."""
    lines = [ln for ln in compiled.as_text().splitlines()
             if re.search(r"%ragged_paged_attention\w*[.\d]* = ", ln)]
    assert len(lines) == 1, lines
    return int(re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                         lines[0]).group(1))


@pytest.mark.parametrize("window", [4096, None], ids=["sliding", "full"])
@pytest.mark.parametrize("T", [1, 64], ids=["decode", "mixed"])
def test_paged_attention_command_a_plus_shapes_compile(one_chip, T, window):
    """The windowed walk and the 1,024-row block (four row tiles over
    KV blocks of 64 pages) fit the chip's VMEM, and the call is what the
    benchmark matches on: the block table first, the pair (output,
    float32 log-sum-exp with a last dimension of 1) with ``q_rows ==
    max(8, T x group)``, the window in the name."""
    compiled = _compile_engine_call(one_chip, CA_B, CA_QH, CA_KVH, T,
                                    CA_LEN // PAGE, CA_PAGES, window)
    rows = max(8, T * CA_QH // CA_KVH)
    assert _paged_call(compiled) == (
        "ragged_paged_attention" + ("_w4096" if window else ""),
        f"bf16[16,8,{rows},128]", f"f32[16,8,{rows},1]", "s32[16,776]")
    if T == 64:
        assert rows == 1024 and pg.row_tile(T, CA_QH // CA_KVH) == 256
        # 8 MiB of KV buffers, 4 MiB of accumulator, 8 MiB of lane-padded
        # m and l, the pipeline's q, output and log-sum-exp blocks: past
        # the 16 MiB a call is given by default, far under the chip's 128
        assert 32 << 20 < _vmem_limit_of(compiled) < 64 << 20


@pytest.mark.parametrize("T", [1, 16], ids=["decode", "mixed"])
def test_paged_attention_falcon_h1_engine_shapes_compile(one_chip, T):
    """The generation cell's engine: 128 slots, 20 query heads over 4 KV
    heads (a group of 5: 80 rows at T = 16, padded to 8 at T = 1), a table
    of 128 pages, a pool of 16,384 pages of 32 KB a layer; one program a
    slot walks all four heads."""
    compiled = _compile_engine_call(one_chip, 128, 20, 4, T, 128, 16384,
                                    None)
    rows = max(8, T * 5)
    assert _paged_call(compiled) == (
        "ragged_paged_attention", f"bf16[128,4,{rows},128]",
        f"f32[128,4,{rows},1]", "s32[128,128]")
    assert _vmem_limit_of(compiled) == 32 << 20


def test_a_bf16_page_of_8_rows_is_refused_by_the_rule():
    """A page is one copy and a head's rows of it are whole tiles of the
    block buffer: 16 rows of bfloat16, 8 of float32, 32 of int8."""
    assert "multiple of 16" in pg.kernel_geometry_error(8, D)
    assert pg.kernel_geometry_error(8, D, dtype="float32") is None
    assert pg.kernel_geometry_error(8, D, interpret=True) is None
    assert "% 32" in pg.kernel_geometry_error(16, D, quantized=True)


@pytest.mark.parametrize("table", [160, 264], ids=["chat", "mixtral_batch"])
@pytest.mark.parametrize("T", [1, 64], ids=["decode", "mixed"])
def test_paged_attention_mistral_engine_shapes_compile(one_chip, T, table):
    """The chat and Mixtral cells' engines: 32 slots, 32 query heads over
    8 KV heads, tables of 160 (2,560 positions) and 264 pages (4,224), a
    pool of 8,448 pages."""
    compiled = _compile_engine_call(one_chip, 32, 32, 8, T, table, 8448,
                                    None)
    rows = max(8, T * 4)
    assert _paged_call(compiled) == (
        "ragged_paged_attention", f"bf16[32,8,{rows},128]",
        f"f32[32,8,{rows},1]", f"s32[32,{table}]")


def test_gmm_with_live_tiles_compiles(one_chip):
    """A share of the experts: 16 held experts of width 4,096, tiles of 128
    rows, the tiles after the live ones parked on one block."""
    m, e, bm = 4096 + 17 * 128, 16, 128
    _compile(one_chip,
             lambda l, r, t, live: gm.gmm(l, r, t, bm=bm, interpret=False,
                                          live_tiles=live),
             ((m, HIDDEN), BF16), ((e, HIDDEN, HIDDEN), BF16),
             ((m // bm,), jnp.int32), ((), jnp.int32))


# ---- the engine's layer scan over a MoE stack (PR 30) ----
# Mixtral 8x7B widths, the batch-docs cell's larger row count (2,048 tokens
# x 2 choices + 8 x 512 = 8,192 rows): what the scan's body hands ``gmm``
MX_L, MX_I, MX_TOKENS = 2, 14336, 2048
MX_BANK = f"bf16[{E},{HIDDEN},{MX_I}]"


def _scan_over_moe_layers(unstacked):
    """Two layers of the engine's ``_moe_ffn`` under a ``lax.scan``: on a
    place's banks unstacked (tuples the body closes over, the layer picked
    by the period's number), or stacked and scanned (PR 29's layout)."""
    from paddle_tpu.inference.generation import EXPERT_BANKS, _moe_ffn
    from paddle_tpu.models.decoder_spec import MoeSpec
    moe = MoeSpec(num_experts=E, top_k=2, dispatch="grouped", block_m=BM)

    def loop(h, router, *banks):
        def body(x, xs):
            r, gw, sliced = xs
            lp = {"mlp.gate.weight": gw,
                  **dict(zip(EXPERT_BANKS, banks if unstacked else sliced))}
            f, _ = _moe_ffn(x, lp, moe, layer=r if unstacked else None)
            return x + f, None
        if unstacked:
            banks = [tuple(banks[i::3]) for i in range(3)]
        layers = jnp.arange(MX_L, dtype=jnp.int32)
        return jax.lax.scan(
            body, h, (layers, router, None if unstacked else banks))[0]
    return loop


@pytest.mark.parametrize("unstacked", [True, False],
                         ids=["unstacked_banks", "scanned_stack"])
def test_layer_scan_reads_expert_banks_where_they_lie(one_chip, monkeypatch,
                                                      unstacked):
    """A scanned bank is sliced, and a slice that feeds a custom call is
    written out first: one copy of a layer's 0.94 GB bank a layer a step
    (27 % of the Mixtral cell's window, ledger PR 29).  On unstacked banks
    each branch of the body's ``lax.switch`` calls ``gmm`` on whole arrays
    and the compiled loop holds no operation that produces a bank."""
    monkeypatch.setattr(gm, "_mode", lambda interpret=None: "tpu")
    up, down = (E, HIDDEN, MX_I), (E, MX_I, HIDDEN)
    lead = () if unstacked else (MX_L,)
    banks = [(lead + s, BF16) for s in (up, up, down)] * \
        (MX_L if unstacked else 1)
    compiled = _compile(one_chip, _scan_over_moe_layers(unstacked),
                        ((1, MX_TOKENS, HIDDEN), BF16),
                        ((MX_L, HIDDEN, E), BF16), *banks)
    text = compiled.as_text()
    assert f"bf16[{M},{MX_I}]" in text          # the cell's row count
    assert text.count('custom_call_target="tpu_custom_call"') == \
        3 * (MX_L if unstacked else 1)
    makes_a_bank = [
        line.strip()[:120] for line in text.splitlines()
        if re.search(r"= bf16\[%d,(%d,%d|%d,%d)\]\S* [a-z\-]+\("
                     % (E, HIDDEN, MX_I, MX_I, HIDDEN), line)
        and not re.search(r" (parameter|get-tuple-element)\(", line)]
    bank_bytes = E * HIDDEN * MX_I * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    if unstacked:
        assert not makes_a_bank, makes_a_bank
        assert temp < bank_bytes
    else:       # what the case above guards against is there when asked for
        assert any("dynamic-slice" in line for line in makes_a_bank), \
            makes_a_bank
        assert temp >= bank_bytes


# ---- both grouped calls as the benchmark knows them (PR 33) ----
# ``gmm_roofline_pct.batch`` (the Mixtral cell) and
# ``gmm_held_roofline_pct.batch`` (the two cells that hold a share) know
# their calls by the first operands; a call that changes them reads ``null``
# in a cell that lists the metric and the driver refuses that run
def _calls_as_the_trace_names_them(compiled, name):
    """The compiled program's custom calls named ``name``, each parsed as
    the benchmark parses a device operation's name.  A trace's name has
    every operand's shape where the compiled text has ``%name``: they are
    put there from the call's ``operand_layout_constraints``."""
    from chipbench.harness.trace_reduce import parse_op
    call = re.compile(r"\s*(?:ROOT )?(%" + name + r"[.\d]* = .* "
                      r"custom-call\()[^)]*(\),"
                      r".* operand_layout_constraints=\{(.*?\})\}.*)")
    ops = [parse_op(m.group(1) + m.group(3) + m.group(2), 0.0, 1.0)
           for m in map(call.match, compiled.as_text().splitlines()) if m]
    assert ops and all(op.is_kernel for op in ops)
    return ops


def _gmm_calls_as_the_trace_names_them(compiled):
    return _calls_as_the_trace_names_them(compiled, "gmm")


@pytest.mark.parametrize("arm", ["whole_bank", "held"])
def test_grouped_calls_keep_the_operands_their_yardsticks_match(
        one_chip, monkeypatch, arm):
    """The whole-bank arm (every expert held; dead tiles named by the tile
    table's negative entries, which Mosaic takes in an index map) matches
    ``grouped_matmul`` alone, priced at the ``F`` entries laid out; the
    held arm (``live_tiles`` second) matches ``grouped_matmul_held``
    alone."""
    from chipbench.kernels import grouped_matmul as whole
    from chipbench.kernels import grouped_matmul_held as held
    from paddle_tpu.inference.generation import EXPERT_BANKS, _moe_ffn
    from paddle_tpu.models.decoder_spec import MoeSpec
    monkeypatch.setattr(gm, "_mode", lambda interpret=None: "tpu")
    if arm == "whole_bank":        # Mixtral 8x7B, a dense 2,048-row step
        moe = MoeSpec(num_experts=E, top_k=2, dispatch="grouped", block_m=BM)
        inter = MX_I
    else:                          # Command A+, 16 of 128 experts here
        moe = MoeSpec(num_experts=128, top_k=8, score="sigmoid", held=16,
                      offset=32, dispatch="grouped", block_m=128)
        inter = HIDDEN
    up, down = (moe.held, HIDDEN, inter), (moe.held, inter, HIDDEN)

    def ffn(h, live, router, *banks):
        lp = {"mlp.gate.weight": router, **dict(zip(EXPERT_BANKS, banks))}
        return _moe_ffn(h, lp, moe, live=live)

    compiled = _compile(
        one_chip, ffn, ((1, MX_TOKENS, HIDDEN), BF16),
        ((MX_TOKENS,), jnp.bool_), ((HIDDEN, moe.num_experts), BF16),
        (up, BF16), (up, BF16), (down, BF16))
    calls = _gmm_calls_as_the_trace_names_them(compiled)
    assert len(calls) == 3
    F = MX_TOKENS * moe.top_k
    for op in calls:
        if arm == "whole_bank":
            got = whole.match(op)
            assert held.match(op) is None
            assert (got["rows"], got["rows_laid_out"]) == (F, F + E * BM)
        else:
            got = held.match(op)
            assert whole.match(op) is None
            assert got["rows_laid_out"] == F + 17 * 128
        assert (got["block_m"], got["experts"]) == (moe.block_m, moe.held)


# ---- sarvam-105b as one chip of four holds it (PR 31) ----
# latent attention: 64 query heads over ONE row [c (512) | k_r (64)] of the
# pool, T = 64 a 4,096-row query block; the cell's engine: 32 slots, 16,640
# positions in pages of 16 (a table 1,040 wide), five layers
SV_HEADS, SV_RANK, SV_ROPE, SV_B, SV_TABLE, SV_L = 64, 512, 64, 32, 1040, 5

_LATENT_CALL = re.compile(
    r"%(ragged_paged_attention_latent)[.\d]* = (\w+\[[\d,]+\])\{[^}]*\} "
    r"custom-call\(.*operand_layout_constraints=\{(\w+\[[\d,]+\])")


def _latent_shapes(T, n_pages):
    i32 = jnp.int32
    return [((SV_B, T, SV_HEADS, SV_RANK), BF16),
            ((SV_B, T, SV_HEADS, SV_ROPE), BF16),
            ((SV_L, n_pages, PAGE, SV_RANK), BF16),
            ((SV_L, n_pages, PAGE // 2, 2 * SV_ROPE), BF16),
            ((SV_B, SV_TABLE), i32), ((SV_B,), i32), ((SV_B,), i32),
            ((SV_B, T, SV_RANK), BF16), ((SV_B, T, SV_ROPE), BF16),
            ((), i32)]


@pytest.mark.parametrize("T", [1, 64], ids=["decode", "mixed"])
def test_latent_attention_sarvam_shapes_compile(one_chip, T):
    """The whole latent pool (33,280 pages: the compressed rows and the
    rotary keys two tokens a row, each page whole tiles of both) read at a
    traced layer; a 4,096-row block in 16 row tiles over KV blocks of 64
    pages fits the chip's VMEM at the limit the wrapper asks for; and the
    call is what the benchmark matches on: the name, the block table
    first, ONE result ``[slots, T x heads, rank]``."""
    assert pg.kernel_geometry_error(PAGE, 0, latent=(SV_RANK, SV_ROPE)) \
        is None
    compiled = _compile(
        one_chip,
        lambda qc, qr, c, r, bt, cl, ql, cn, rn, ly:
        pal._pallas_ragged_paged_attention_latent(
            qc, qr, c, r, bt, cl, ql, cn, rn, interpret=False, scale=0.1352,
            layer=ly),
        *_latent_shapes(T, 33280))
    calls = _LATENT_CALL.findall(compiled.as_text())
    rows = max(8, T * SV_HEADS)
    assert calls == [("ragged_paged_attention_latent",
                      f"bf16[32,{rows},512]", "s32[32,1040]")]
    if T == 64:
        # the latent call's own tile, not the per-head kernel's constant
        tile = pal.latent_row_tile(T, SV_HEADS)
        assert rows == 4096 and tile == pal._LATENT_SCHEDULE[False][0]
        assert pg.row_tile(T, SV_HEADS) == 256


def test_a_64_wide_pool_is_refused_by_the_compiler_and_by_the_rule(one_chip):
    """ROADMAP M12: rows half a lane tile wide cannot be sliced out of a
    pool (per-head pages of ``head_dim`` 64, or the rotary keys one token a
    row): the compiler's reason is the one ``kernel_geometry_error`` gives,
    which is why the latent pool keeps two tokens' rotary keys a row."""
    assert "multiple of 128" in pg.kernel_geometry_error(PAGE, 64)
    i32, d = jnp.int32, 64
    cache = ((N_PAGES, 2, KVH, PAGE, d), BF16)
    with pytest.raises(Exception, match=r"aligned to tiling \(128\)"):
        _compile(one_chip,
                 lambda q, kv, bt, cl, ql, kn, vn:
                 pa._pallas_ragged_paged_attention(q, kv, bt, cl, ql, kn,
                                                   vn, False),
                 ((BATCH, 1, QH, d), BF16), cache,
                 ((BATCH, W), i32), ((BATCH,), i32), ((BATCH,), i32),
                 ((BATCH, 1, KVH, d), BF16), ((BATCH, 1, KVH, d), BF16))


def test_sarvam_step_program_compiles_at_published_widths(one_chip,
                                                          monkeypatch):
    """The packed T = 64 step program of the cell's engine (32 slots, 512
    GEMM rows) at the published widths, on abstract parameters: the leading
    dense layer unrolled before a scan over four expert layers, two latent
    calls (one in the leading layer, one in the scan's body) and three
    grouped GEMMs on each layer's own banks, no copy of a bank or of the
    pool, and it fits the chip beside its 9.07 GB of weights (the pool here
    is a sixteenth of the cell's: its size moves no operation but the
    commit's bounds)."""
    from paddle_tpu.inference import generation as gen
    from paddle_tpu.models.sarvam_mla import (SarvamMlaConfig,
                                              SarvamMlaForCausalLM,
                                              layer_leaves)

    monkeypatch.setattr(pal, "jax", _OnTpu())
    monkeypatch.setattr(gm, "_mode", lambda interpret=None: "tpu")
    cfg = SarvamMlaConfig.sarvam_105b(
        num_hidden_layers=SV_L, experts_held=32, vocab_size=65536,
        max_position_embeddings=16640)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)

    class Abstract:
        config = cfg
        decoder_spec = SarvamMlaForCausalLM.decoder_spec

        def serving_params(self):
            H, V, n = cfg.hidden_size, cfg.vocab_size, SV_L - 1
            experts = {
                name: tuple(sds(shape, dt) for _ in range(n))
                if name in gen.EXPERT_BANKS else sds((n,) + tuple(shape), dt)
                for name, shape, _, dt in layer_leaves(cfg, False)}
            return {"embed": sds((V, H), BF16), "norm": sds((H,), BF16),
                    "head": sds((H, V), BF16),
                    "leading": ({name: sds(shape, dt) for name, shape, _, dt
                                 in layer_leaves(cfg, True)},),
                    "blocks": (experts,)}

    g = gen.LlamaGenerator(Abstract(), max_batch=SV_B, max_seq_len=16640,
                           page_size=PAGE, prefill_bucket=64, num_pages=2080)
    params = g.params
    held = sum(int(jnp.prod(jnp.asarray(a.shape))) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(params))
    assert abs(held / 2 - 4.535e9) < 1e6          # parameters, bf16
    T, rows = 64, g.row_buckets(64)[0]
    assert rows == 512
    i32, key = jnp.int32, jax.random.key(0)
    vec = lambda dt: sds((SV_B,), dt)             # noqa: E731
    ops = (params, tuple(sds(a.shape, a.dtype) for a in g.cache.arrays),
           sds((SV_B, T), i32), vec(i32), vec(i32), vec(jnp.bool_),
           vec(jnp.bool_), vec(jnp.bool_), vec(i32), vec(i32),
           sds((SV_B, g.pages_per_seq), i32),
           jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip))
    compiled = g._step_jit(gen.GenerationConfig(), T, False, rows) \
        .lower(*ops).compile()
    text = compiled.as_text()
    assert len(_LATENT_CALL.findall(text)) == 2
    assert text.count('custom_call_target="tpu_custom_call"') == \
        2 + 3 * (SV_L - 1)
    makes_a_bank = [
        line.strip()[:120] for line in text.splitlines()
        if re.search(r"= bf16\[32,(4096,2048|2048,4096)\]\S* [a-z\-]+\(", line)
        and not re.search(r" (parameter|get-tuple-element)\(", line)]
    assert not makes_a_bank, makes_a_bank
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30
    assert mem.alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in g.cache.arrays)   # pool in place


# ---- falcon-h1-34b at depth 4 (PR 34) ----
# a Mamba-2 mixer beside GQA 20 / 4 x 128: the scan call over a float32
# state [layers, slots, 32, 128, 256] and the cell's engine: 128 slots,
# 2,048 positions in pages of 16, chunks of 16, four layers
FH_B, FH_L, FH_H, FH_P, FH_N, FH_G = 128, 4, 32, 128, 256, 2


@pytest.mark.timeout(300)
@pytest.mark.parametrize("T", [1, 16], ids=["decode", "mixed"])
def test_ssd_update_falcon_shapes_compile(one_chip, T):
    """The scan call at the published widths and 128 slots, on the whole
    state read at a traced layer: one group's 16 heads a program (a 2 MiB
    state block in and out), the state result aliased to its operand (the
    2.15 GB are the call's only large buffer), and the call is what the
    benchmark matches on: the name, ``y [slots, heads, rows, 128]`` first,
    the float32 state second and among the operands."""
    from chipbench.kernels import ssd_update
    from paddle_tpu.kernels import ssd
    assert ssd._heads_per_block(FH_H // FH_G, FH_P, FH_N) == 16
    f32, i32 = jnp.float32, jnp.int32
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((FH_L, FH_B, FH_H, FH_P, FH_N), f32), ((FH_B, T, FH_H, FH_P), BF16),
        ((FH_B, T, FH_G, FH_N), BF16), ((FH_B, T, FH_G, FH_N), BF16),
        ((FH_B, T, FH_H), f32), ((FH_H,), f32), ((FH_H,), f32),
        ((FH_B,), i32), ((FH_B,), jnp.bool_), ((), i32))]
    compiled = jax.jit(
        lambda *a: ssd._pallas_ragged_ssd_update(*a, interpret=False),
        donate_argnums=(0,)).lower(*args).compile()
    state_bytes = FH_L * FH_B * FH_H * FH_P * FH_N * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes            # in place
    assert mem.temp_size_in_bytes < 64 << 20
    op, = _calls_as_the_trace_names_them(compiled, "ragged_ssd_update")
    assert ssd_update.match(op) == {
        "slots": FH_B, "heads": FH_H, "head_dim": FH_P, "state": FH_N,
        "q_rows": max(8, T), "dtype": "bf16"}


@pytest.mark.timeout(600)
def test_falcon_h1_step_program_compiles_at_published_widths(one_chip,
                                                             monkeypatch):
    """The packed T = 16 step program of the cell's engine (128 slots, 512
    GEMM rows) at the published widths, on abstract parameters: a scan over
    four layers whose body holds one paged call (a group of 5: row tiles of
    80) and one scan call on the carried state; pool AND state are updated
    in place (no second ``[4, 128, 32, 128, 256]`` float32 buffer), and the
    program fits the chip beside its 8.79 GB of weights, 2.16 GB of state
    and the cell's 2.15 GB pool (the pool here is an eighth of the cell's:
    its size moves no operation but the commit's bounds, and the peak below
    counts the cell's)."""
    from paddle_tpu.inference import generation as gen
    from paddle_tpu.kernels import ssd
    from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                             FalconH1ForCausalLM,
                                             layer_leaves)

    monkeypatch.setattr(pa, "jax", _OnTpu())
    monkeypatch.setattr(ssd, "jax", _OnTpu())
    cfg = FalconH1Config.falcon_h1_34b(num_hidden_layers=FH_L,
                                       max_position_embeddings=2048)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)

    class Abstract:
        config = cfg
        decoder_spec = FalconH1ForCausalLM.decoder_spec

        def serving_params(self):
            H, V = cfg.hidden_size, cfg.vocab_size
            return {"embed": sds((V, H), BF16), "norm": sds((H,), BF16),
                    "head": sds((H, V), BF16),
                    "blocks": ({name: sds((FH_L,) + tuple(shape), dt)
                                for name, shape, _, dt
                                in layer_leaves(cfg)},)}

    pages, cell_pages = 2048, 16384
    g = gen.LlamaGenerator(Abstract(), max_batch=FH_B, max_seq_len=2048,
                           page_size=PAGE, prefill_bucket=16,
                           num_pages=pages)
    leaves = jax.tree_util.tree_leaves(g.params)
    assert sum(int(jnp.prod(jnp.asarray(a.shape))) for a in leaves) == \
        4_394_354_048
    assert g.state_bytes_per_slot == 16_900_096
    T, rows = 16, g.row_buckets(16)[0]
    assert g.row_buckets(16) == [512, 2048]
    assert pg.row_tile(T, 5) == 80 and pg.row_tile(1, 5) == 8
    i32, key = jnp.int32, jax.random.key(0)
    vec = lambda dt: sds((FH_B,), dt)             # noqa: E731
    ops = (g.params, tuple(sds(a.shape, a.dtype) for a in g.cache.arrays),
           sds((FH_B, T), i32), vec(i32), vec(i32), vec(jnp.bool_),
           vec(jnp.bool_), vec(jnp.bool_), vec(i32), vec(i32),
           sds((FH_B, g.pages_per_seq), i32),
           jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip))
    compiled = g._step_jit(gen.GenerationConfig(), T, False, rows) \
        .lower(*ops).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert len(_calls_as_the_trace_names_them(
        compiled, "ragged_ssd_update")) == 1
    mem = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in g.cache.arrays)
    assert mem.alias_size_in_bytes == held       # pool and state in place
    assert mem.temp_size_in_bytes < 512 << 20
    # the commit leaves the pool where it lies: no copy of it into another
    # layout and back (a window of one row a token with the page AND the
    # offset dynamic made XLA move the whole pool twice a step, PR 35)
    assert not _pool_shaped_copies(text, g.cache.kv)
    per_page = gen.PagedKVCache.bytes_per_page(FH_L, 4, PAGE, 128, "bfloat16")
    peak = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes \
        + (cell_pages - pages) * per_page
    assert 13.0e9 < peak < 15.5e9, peak


# ---- deepseek-v3.2 as one chip of sixteen holds it (PR 39) ----
# a learned index over the latent pool: 64 index heads of 128 score every
# cached token (one index key a token a layer, the pool's third plane),
# each query token's best 2,048 are chosen exactly, and 128 query heads
# read ONE row [c (512) | k_r (64)] masked to the set; the cell's engine:
# 8 slots, 33,024 positions in pages of 16 (a table 2,064 wide), five layers
DS_HEADS, DS_IH, DS_DIM, DS_B, DS_TABLE, DS_L = 128, 64, 128, 8, 2064, 5
DS_PAGES = DS_B * DS_TABLE


def _ds(one_chip):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)
    return sds


@pytest.mark.parametrize("T", [1, 64], ids=["decode", "mixed"])
def test_latent_index_calls_deepseek_shapes_compile(one_chip, T):
    """The three calls of the learned sparse attention at the cell's sizes,
    the whole three-plane pool read at a traced layer, each named as the
    benchmark's matchers want it: the scores over paged index keys (one
    float32 result ``[slots, tokens, keys]``), the exact selection (the
    rows of 16 query tokens in VMEM, a bfloat16 0/1 result) and the masked
    walk (``[slots, T x heads, rank]``, the block's 0/1 rows copied beside
    its pages)."""
    from paddle_tpu.kernels import latent_index as li
    from chipbench.kernels import (latent_index as k_index,
                                   paged_attention_latent,
                                   paged_attention_latent_sparse as k_sparse)
    sds, i32, f32 = _ds(one_chip), jnp.int32, jnp.float32
    assert li.index_geometry_error(PAGE, DS_DIM) is None
    assert "multiple of 16" in li.index_geometry_error(8, DS_DIM)
    S = DS_TABLE * PAGE
    table, vec = sds((DS_B, DS_TABLE), i32), sds((DS_B,), i32)

    def named(compiled, name):
        (op,) = _calls_as_the_trace_names_them(compiled, name)
        return op

    scores = jax.jit(
        lambda q, w, k, bt, cl, ql, ly: li._pallas_latent_index_scores(
            q, w, k, bt, cl, ql, interpret=False, layer=ly)).lower(
        sds((DS_B, T, DS_IH, DS_DIM), BF16), sds((DS_B, T, DS_IH), f32),
        sds((DS_L, DS_PAGES, PAGE, DS_DIM), BF16), table, vec, vec,
        sds((), i32)).compile()
    Tp = -(-T // 8) * 8
    assert k_index.match(named(scores, "latent_index_scores")) == {
        "kind": "scores", "slots": DS_B, "tokens": Tp, "heads": DS_IH,
        "dim": DS_DIM}
    select = jax.jit(
        lambda s, k, ql, cl: li._pallas_latent_index_select(
            s, k, ql, cl, interpret=False, n_new=T)).lower(
        sds((DS_B, T, S + T), f32), sds((DS_B, T), i32), vec, vec).compile()
    got = k_index.match(named(select, "latent_index_select"))
    assert got["kind"] == "select" and got["tokens"] == -(-T // 16) * 16
    sparse = jax.jit(
        lambda qc, qr, c, r, bt, cl, ql, cn, rn, sel, ly:
        pal._pallas_ragged_paged_attention_latent(
            qc, qr, c, r, bt, cl, ql, cn, rn, interpret=False, scale=0.1352,
            layer=ly, selected=sel)).lower(
        sds((DS_B, T, DS_HEADS, SV_RANK), BF16),
        sds((DS_B, T, DS_HEADS, SV_ROPE), BF16),
        sds((DS_L, DS_PAGES, PAGE, SV_RANK), BF16),
        sds((DS_L, DS_PAGES, PAGE // 2, 2 * SV_ROPE), BF16), table, vec, vec,
        sds((DS_B, T, SV_RANK), BF16), sds((DS_B, T, SV_ROPE), BF16),
        sds((DS_B, T, S + T), jnp.bool_), sds((), i32)).compile()
    op = named(sparse, "ragged_paged_attention_latent_sparse")
    assert k_sparse.match(op) == {
        "slots": DS_B, "q_rows": max(8, T * DS_HEADS), "rank": SV_RANK,
        "rope": SV_ROPE, "dtype": "bf16"}
    # neither yardstick takes the other's call
    assert paged_attention_latent.match(op) is None
    assert k_index.match(op) is None
    assert k_sparse.match(named(scores, "latent_index_scores")) is None


@pytest.mark.timeout(900)
def test_deepseek_v32_step_program_compiles_at_published_widths(one_chip,
                                                                monkeypatch):
    """The packed T = 64 step program of the cell's engine (8 slots, 256
    GEMM rows) at the published widths, on abstract parameters: the leading
    dense layer unrolled before a scan over four expert layers; each of the
    two holds the three calls of the learned sparse attention, the expert
    layers three grouped GEMMs on their own banks; the three-plane pool is
    committed in place, and the program fits the chip beside its 9.27 GB of
    weights and the cell's 1.86 GB pool (the pool here is an eighth of the
    cell's: its size moves no operation but the commit's bounds)."""
    from paddle_tpu.inference import generation as gen
    from paddle_tpu.kernels import latent_index as li
    from paddle_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                                DeepseekV32ForCausalLM,
                                                layer_leaves)

    monkeypatch.setattr(pal, "jax", _OnTpu())
    monkeypatch.setattr(li, "jax", _OnTpu())
    monkeypatch.setattr(gm, "_mode", lambda interpret=None: "tpu")
    cfg = DeepseekV32Config.deepseek_v32_ep16(max_position_embeddings=33024)
    sds = _ds(one_chip)

    class Abstract:
        config = cfg
        decoder_spec = DeepseekV32ForCausalLM.decoder_spec

        def serving_params(self):
            H, V, n = cfg.hidden_size, cfg.vocab_size, DS_L - 1
            experts = {
                name: tuple(sds(shape, dt) for _ in range(n))
                if name in gen.EXPERT_BANKS else sds((n,) + tuple(shape), dt)
                for name, shape, _, dt in layer_leaves(cfg, False)}
            return {"embed": sds((V, H), BF16), "norm": sds((H,), BF16),
                    "head": sds((H, V), BF16),
                    "leading": ({name: sds(shape, dt) for name, shape, _, dt
                                 in layer_leaves(cfg, True)},),
                    "blocks": (experts,)}

    pages = DS_PAGES // 8
    g = gen.LlamaGenerator(Abstract(), max_batch=DS_B, max_seq_len=33024,
                           page_size=PAGE, prefill_bucket=64, num_pages=pages)
    assert g.pool_bytes // (pages * PAGE) == 7040
    n_params = sum(int(jnp.prod(jnp.asarray(a.shape)))
                   for a in jax.tree_util.tree_leaves(g.params))
    assert n_params == 4_635_518_208
    T, rows = 64, g.row_buckets(64)[0]
    assert g.row_buckets(64) == [256, 512]
    i32, key = jnp.int32, jax.random.key(0)
    vec = lambda dt: sds((DS_B,), dt)             # noqa: E731
    ops = (g.params, tuple(sds(a.shape, a.dtype) for a in g.cache.arrays),
           sds((DS_B, T), i32), vec(i32), vec(i32), vec(jnp.bool_),
           vec(jnp.bool_), vec(jnp.bool_), vec(i32), vec(i32),
           sds((DS_B, g.pages_per_seq), i32),
           jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip))
    compiled = g._step_jit(gen.GenerationConfig(), T, False, rows) \
        .lower(*ops).compile()
    text = compiled.as_text()
    for name in ("latent_index_scores", "latent_index_select",
                 "ragged_paged_attention_latent_sparse"):
        assert len(_calls_as_the_trace_names_them(compiled, name)) == 2, name
    assert text.count('custom_call_target="tpu_custom_call"') == \
        3 * 2 + 3 * (DS_L - 1)
    mem = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in g.cache.arrays)
    assert mem.alias_size_in_bytes == held        # three planes in place
    cell_pool = DS_PAGES * PAGE * 7040
    peak = 2 * n_params + cell_pool + mem.temp_size_in_bytes
    assert peak < 15.5e9, (peak, mem.temp_size_in_bytes)


# ---- smallthinker-21ba3b-instruct, depth 8 with every expert (PR 41) ----
# 28 query heads over 4 KV heads (a group of 7: 448 rows at T = 64, padded
# to 8 at T = 1), with and without a window of 4,096 in one stack; 64 ReGLU
# experts of 768 over a hidden of 2,560, all held; the cell's engine: 48
# slots, 5,120 positions in pages of 16 (a table 320 wide), a pool of 15,360
ST_QH, ST_KVH, ST_B, ST_TABLE, ST_PAGES = 28, 4, 48, 320, 15360
ST_H, ST_I, ST_E, ST_BM, ST_ROWS = 2560, 768, 64, 128, 768


@pytest.mark.parametrize("window", [4096, None], ids=["windowed", "full"])
@pytest.mark.parametrize("T", [1, 64], ids=["decode", "mixed"])
def test_paged_attention_smallthinker_engine_shapes_compile(one_chip, T,
                                                            window):
    """A group of seven (no power of two) over pages of 32 KB a layer, the
    windowed and the full call of one stack: each is what the benchmark
    matches on, the window in the name."""
    compiled = _compile_engine_call(one_chip, ST_B, ST_QH, ST_KVH, T,
                                    ST_TABLE, ST_PAGES, window, layers=8)
    rows = max(8, T * ST_QH // ST_KVH)
    assert _paged_call(compiled) == (
        "ragged_paged_attention" + ("_w4096" if window else ""),
        f"bf16[48,4,{rows},128]", f"f32[48,4,{rows},1]", "s32[48,320]")
    if T == 64:
        assert rows == 448 and pg.row_tile(T, 7) == 224


@pytest.mark.parametrize("k,n", [(ST_H, ST_I), (ST_I, ST_H)],
                         ids=["gate_and_up", "down"])
def test_gmm_smallthinker_shapes_compile(one_chip, k, n):
    """``[M, 2560] x [64, 2560, 768]`` and ``[M, 768] x [64, 768, 2560]``
    with the dead tiles named in the table (the whole-bank arm), at the
    768-row packed member: M = 768 x 6 entries + 64 tiles of 128."""
    m = ST_ROWS * 6 + ST_E * ST_BM
    assert gm._pick_block(ST_I, 512) == 256 and gm._pick_block(ST_H, 512) == 512
    _compile(one_chip,
             lambda l, r, t: gm.gmm(l, r, t, bm=ST_BM, interpret=False,
                                    dead_in_table=True),
             ((m, k), BF16), ((ST_E, k, n), BF16), ((m // ST_BM,), jnp.int32))


def test_smallthinker_expert_layer_compiles_and_its_yardstick_matches(
        one_chip, monkeypatch):
    """The expert layer as the step calls it at the published widths: the
    choice made apart (on another tensor than the experts read), ReLU
    between the grouped calls, the fullest expert counted; its three calls
    match ``grouped_matmul`` (and not the held arm's matcher), with the row
    bucket's ``768 x 6`` entries as ``rows``: what ``gmm_roofline_pct.batch``
    would price, and why the cell reports ``gmm_counted_roofline_pct.batch``
    (the program's own count) instead."""
    from chipbench.kernels import grouped_matmul as whole
    from chipbench.kernels import grouped_matmul_held as held
    from paddle_tpu.inference.generation import (EXPERT_BANKS, _moe_choice,
                                                 _moe_experts)
    from paddle_tpu.models.smallthinker import SmallThinkerConfig
    monkeypatch.setattr(gm, "_mode", lambda interpret=None: "tpu")
    moe = SmallThinkerConfig.smallthinker_21b().moe_spec()
    up, down = (ST_E, ST_H, ST_I), (ST_E, ST_I, ST_H)

    def ffn(u, z, live, router, *banks):
        lp = {"mlp.gate.weight": router, **dict(zip(EXPERT_BANKS, banks))}
        return _moe_experts(z, lp, moe, _moe_choice(u, lp, moe), live=live)

    compiled = _compile(
        one_chip, ffn, ((1, ST_ROWS, ST_H), BF16), ((1, ST_ROWS, ST_H), BF16),
        ((ST_ROWS,), jnp.bool_), ((ST_H, ST_E), BF16), (up, BF16),
        (up, BF16), (down, BF16))
    calls = _gmm_calls_as_the_trace_names_them(compiled)
    assert len(calls) == 3
    for op in calls:
        got = whole.match(op)
        assert held.match(op) is None
        assert (got["rows"], got["rows_laid_out"], got["block_m"],
                got["experts"]) == (ST_ROWS * 6, ST_ROWS * 6 + ST_E * ST_BM,
                                    ST_BM, ST_E)
        # priced with what a steady step holds (about 1,200 entries) the
        # call is bound by its bank's bytes, not by its products
        flops, nbytes = held.cost(got, 1200.0)
        assert flops / 197e12 < nbytes / 819e9


# ---- the dp 2 x mp 2 train step of mistral-7b-v0.3 (PR 44) ----
# `PretrainStep` on a mesh of the DESCRIBED chips, the state and the batch
# as shapes that carry its own shardings: the program of the cell
# `mistral7b-train-dp2mp2`, two layers deep (the layers are a loop)

# ---- solar-open2-250b as one chip of eight holds it (PR 46) ----
# gated delta-rule linear attention on three layers in four: 64 heads over a
# float32 state [3, slots, 64, 128, 128], the step's tokens PACKED (a block
# may begin at any row); the cell's engine: 192 slots, 2,560 positions in
# pages of 16, chunks of 64, one period of four layers, 40 of 320 experts
SO_B, SO_L, SO_H, SO_D = 192, 3, 64, 128


@pytest.mark.timeout(300)
@pytest.mark.parametrize("rows,chunk", [(SO_B, 1), (3072, 64), (12288, 64)],
                         ids=["decode", "packed", "dense_grid"])
def test_kda_update_solar_open2_shapes_compile(one_chip, rows, chunk):
    """The delta-rule call at the published widths and 192 slots, on the
    whole state read at a traced layer: 16 heads a program (a 1 MiB state
    block in and out), a decode slot's tokens a block of one row and a
    chunk's a block of 64 rows at an element offset, the state result
    aliased to its operand (2.4 GB: the call's only large buffer), and the
    call is what the benchmark matches on: the name, the decode slots' rows
    first, the float32 state last and among the operands."""
    from chipbench.kernels import kda_update
    from paddle_tpu.kernels import kda
    assert kda._heads_per_block(SO_H, SO_D, SO_D) == 16
    assert kda.kda_geometry_error(SO_H, SO_D, SO_D) is None
    f32, i32 = jnp.float32, jnp.int32
    token = (rows, SO_H, SO_D)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((SO_L, SO_B, SO_H, SO_D, SO_D), f32), (token, BF16), (token, f32),
        (token, f32), (token, f32), ((rows, SO_H), f32), ((SO_B,), i32),
        ((SO_B,), i32), ((SO_B,), jnp.bool_), ((), i32))]
    compiled = jax.jit(
        lambda s, *a: kda._pallas_ragged_kda_update(
            s, *a[:-1], chunk, a[-1], False),
        donate_argnums=(0,)).lower(*args).compile()
    state_bytes = SO_L * SO_B * SO_H * SO_D * SO_D * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes            # in place
    # the chunks' rows come back in [slots, chunk, heads, 128]: 201 MB that
    # only chunk slots' blocks are written to; beside it the folded k, v
    assert mem.temp_size_in_bytes < (64 << 20) + (
        SO_B * chunk * SO_H * SO_D * 2 + 3 * rows * SO_H * SO_D * 2
        if chunk > 1 else 0)
    op, = _calls_as_the_trace_names_them(compiled, "ragged_kda_update")
    assert kda_update.match(op) == {
        "slots": SO_B, "heads": SO_H, "key_dim": SO_D, "value_dim": SO_D,
        "chunk": chunk, "dtype": "bf16"}


@pytest.mark.timeout(600)
def test_solar_open2_step_program_compiles_at_published_widths(one_chip,
                                                               monkeypatch):
    """The packed T = 64 step program of the cell's engine (192 slots,
    3,072 GEMM rows) at the published widths, on abstract parameters: one
    period in line, its softmax place one paged call (a group of 8) and
    its three linear places one delta-rule call each on the carried state,
    every place three grouped GEMMs over 40 held experts; the pool (ONE
    page layer) and the state (three) are updated in place, and the
    program fits the chip beside its 6.62 GB of weights, 2.50 GB of state
    and the cell's 2.01 GB pool (the pool here is a fifteenth of the
    cell's: its size moves no operation but the commit's bounds)."""
    from paddle_tpu.inference import generation as gen
    from paddle_tpu.kernels import kda
    from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                               SolarOpen2ForCausalLM,
                                               layer_leaves)

    monkeypatch.setattr(pa, "jax", _OnTpu())
    monkeypatch.setattr(kda, "jax", _OnTpu())
    monkeypatch.setattr(gm, "_mode", lambda interpret=None: "tpu")
    cfg = SolarOpen2Config.solar_open2_250b(max_position_embeddings=2560)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)

    class Abstract:
        config = cfg
        decoder_spec = SolarOpen2ForCausalLM.decoder_spec

        def serving_params(self):
            H, V = cfg.hidden_size, cfg.vocab_size
            return {"embed": sds((V, H), BF16), "norm": sds((H,), BF16),
                    "head": sds((H, V), BF16),
                    "blocks": tuple({
                        name: (sds(shape, dt),) if name in gen.EXPERT_BANKS
                        else sds((1,) + tuple(shape), dt)
                        for name, shape, _, dt in layer_leaves(cfg, p > 0)}
                        for p in range(4))}

    pages, cell_pages = 2048, 30720
    g = gen.LlamaGenerator(Abstract(), max_batch=SO_B, max_seq_len=2560,
                           page_size=PAGE, prefill_bucket=64,
                           num_pages=pages)
    n_params = sum(int(jnp.prod(jnp.asarray(a.shape)))
                   for a in jax.tree_util.tree_leaves(g.params))
    assert n_params == 3_308_353_344
    assert g.state_bytes_per_slot == 13_025_280
    assert g.pool_bytes // (pages * PAGE) == 4096
    assert [a.shape for a in g.cache.arrays] == [
        (1, pages, 2, 8, PAGE, 128), (3, SO_B, 64, 128, 128),
        (3, SO_B, 3, 24576)]
    T, rows = 64, g.row_buckets(64)[0]
    assert g.row_buckets(64) == [3072, 12288]
    i32, key = jnp.int32, jax.random.key(0)
    vec = lambda dt: sds((SO_B,), dt)             # noqa: E731
    ops = (g.params, tuple(sds(a.shape, a.dtype) for a in g.cache.arrays),
           sds((SO_B, T), i32), vec(i32), vec(i32), vec(jnp.bool_),
           vec(jnp.bool_), vec(jnp.bool_), vec(i32), vec(i32),
           sds((SO_B, g.pages_per_seq), i32),
           jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip))
    compiled = g._step_jit(gen.GenerationConfig(), T, False, rows) \
        .lower(*ops).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 + 3 + 4 * 3
    assert len(_calls_as_the_trace_names_them(
        compiled, "ragged_kda_update")) == 3
    mem = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in g.cache.arrays)
    assert mem.alias_size_in_bytes == held       # pool and state in place
    assert mem.temp_size_in_bytes < 1536 << 20
    assert not _pool_shaped_copies(text, g.cache.kv)
    peak = 2 * n_params + SO_B * 13_025_280 + cell_pages * PAGE * 4096 \
        + mem.temp_size_in_bytes
    assert 11.5e9 < peak < 13.5e9, peak


def _abstract_train_step(topo, monkeypatch, layout, layers, batch,
                         seq=4096, **widths):
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu import flags
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import (ParallelConfig, PretrainStep,
                                            build_mesh)

    monkeypatch.setattr(fa, "jax", _OnTpu())
    # the tile probe would run the kernel: the cells turn it off too
    monkeypatch.setitem(flags._VALUES, "autotune_enable", False)
    cfg = LlamaConfig(**dict(dict(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=layers, num_attention_heads=32,
        num_key_value_heads=8, max_position_embeddings=4096,
        rms_norm_eps=1e-5, rope_theta=1e6, dtype="bfloat16"), **widths))
    pc = ParallelConfig(remat=True, **layout)
    mesh = build_mesh(pc, np.asarray(topo.devices))
    ps = PretrainStep(cfg, pc, mesh=mesh)
    shapes = {"embed": (cfg.vocab_size, cfg.hidden_size),
              "head": (cfg.hidden_size, cfg.vocab_size),
              "norm": (cfg.hidden_size,),
              "blocks": ps._block_shapes()}
    is_shape = lambda x: isinstance(x, tuple)             # noqa: E731
    sh = ps._shardings(jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, BF16), shapes, is_leaf=is_shape))
    like = lambda dt: jax.tree_util.tree_map(             # noqa: E731
        lambda s, at: jax.ShapeDtypeStruct(s, dt, sharding=at), shapes, sh,
        is_leaf=is_shape)
    state = {"params": like(BF16), "m": like(jnp.float32),
             "v": like(jnp.float32),
             "step": jax.ShapeDtypeStruct((), jnp.int32,
                                          sharding=NamedSharding(mesh, P()))}
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", None)))
    return ps, state, ids


@pytest.mark.timeout(600)
def test_the_four_chip_train_step_starts_its_sums_and_runs_on(topo, one_chip,
                                                              monkeypatch):
    """Compiled as ``PretrainStep`` itself builds it for the 2 x 2 mesh, the
    step holds asynchronous sums (start, the product it runs under, done):
    of the activation sums (mp) two a backward-loop body and the head's;
    of the dp gradient sums every weight matrix's in the loop, each its own
    instruction.  What is left to stop the core is what has no independent
    work inside one layer over one batch (the activation sums on the
    critical path), vectors, and the head's and embedding's gradients
    after the loop."""
    from paddle_tpu.models.pretrain import _ASYNC_SUMS
    from paddle_tpu.observability.collectives import find_all_reduces
    ps, state, ids = _abstract_train_step(topo, monkeypatch, dict(dp=2, mp=2), 2, 4)
    assert ps._compile_kwargs() == {"compiler_options": _ASYNC_SUMS}
    compiled = ps.lowered_step(state, ids, ids).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4  # flash
    found = find_all_reduces(text)
    act = "bf16[2,4096,4096]"
    started = [(entry, typ) for entry, is_async, typ in found if is_async]
    assert started.count((False, act)) >= 2, found
    assert (True, act) in started, found
    # each leaf of a layer's weights: q, k, v, o, gate, up, down
    weights = sorted(typ for entry, typ in started
                     if not entry and typ.startswith("bf16[1,"))
    assert weights == sorted(
        ["bf16[1,4096,2048]", "bf16[1,4096,512]", "bf16[1,4096,512]",
         "bf16[1,2048,4096]", "bf16[1,4096,7168]", "bf16[1,4096,7168]",
         "bf16[1,7168,4096]"]), found
    standing = [(entry, typ) for entry, is_async, typ in found
                if not is_async]
    assert not [typ for _, typ in standing if typ.startswith("(bf16[1,")], \
        "a combined gradient sum is back in the loop: " + repr(standing)
    in_loops = sorted(typ for entry, typ in standing if not entry)
    assert in_loops == sorted([act] * 3 + ["bf16[4096]"]), found
    assert ps.count_collectives(state, ids, ids) == \
        (len(found), len(started))
    # the pairs hold their operands a little longer: 13.8 GiB at depth 4
    # was the parent's reckoning, a chip has 16
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 12 << 30


@pytest.mark.timeout(600)
def test_the_one_chip_train_step_is_compiled_with_no_option(topo, one_chip,
                                                            monkeypatch):
    """One device has no collective: ``jax.jit`` is handed nothing, so the
    program and its cache key stay what they were."""
    from paddle_tpu.observability.collectives import find_all_reduces
    ps, state, ids = _abstract_train_step(topo, monkeypatch, {}, 1, 1)
    assert ps._compile_kwargs() == {}
    compiled = ps.lowered_step(state, ids, ids).compile()
    assert find_all_reduces(compiled.as_text()) == []
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 4


def _sum_bytes(typ):
    """Bytes of an all-reduce's result, a combined (tuple) one's together."""
    import math
    return sum(math.prod(int(d) for d in dims.split(",") if d)
               * (2 if dt == "bf16" else 4)
               for dt, dims in re.findall(r"(bf16|f32)\[([\d,]*)\]", typ))


@pytest.mark.timeout(900)
@pytest.mark.parametrize("layout,layers,batch,seq,widths", [
    (dict(dp=2, ep=2), 2, 4, 4096,
     dict(moe_num_experts=8, intermediate_size=3584)),
    (dict(pp=2, micro_batches=2), 2, 2, 1000, {})],
    ids=["moe_dp2_ep2", "pp2"])
def test_other_layouts_sums_do_not_blow_up_under_the_options(
        topo, one_chip, monkeypatch, layout, layers, batch, seq, widths):
    """``_ASYNC_SUMS`` reaches EVERY step program on several TPU chips, and
    was measured on the dense dp 2 x mp 2 one alone.  What a compile for the
    described chips can say of two others (an expert-parallel MoE step: 8
    experts of 3,584 a layer, router and vectors beside the banks; a
    two-stage pipeline, whose stages pass activations by permutes), each
    compiled without and with the options: the options ADD no sum (every
    all-reduce with them is one member of a sum the plain compile holds,
    alone or combined), the core stops in a loop for no more sums and no
    more bytes than before, and the temporaries grow by less than a sixth.
    No time is read here: a training cell of such a layout has to measure
    the options again (ROADMAP S5)."""
    from paddle_tpu.models.pretrain import _ASYNC_SUMS
    from paddle_tpu.observability.collectives import find_all_reduces

    def all_reduces_and_temporaries(options):
        ps, state, ids = _abstract_train_step(
            topo, monkeypatch, layout, layers, batch, seq, **widths)
        assert ps._compile_kwargs() == {"compiler_options": _ASYNC_SUMS}
        if not options:
            monkeypatch.setattr(ps, "_compile_kwargs", lambda: {})
        compiled = ps.lowered_step(state, ids, ids).compile()
        return (find_all_reduces(compiled.as_text()),
                compiled.memory_analysis().temp_size_in_bytes)

    def standing(sums, in_loops):
        """The synchronous ones' types: those in loop bodies, or all."""
        return [typ for entry, is_async, typ in sums
                if not is_async and not (in_loops and entry)]

    plain, plain_temp = all_reduces_and_temporaries(options=False)
    found, temp = all_reduces_and_temporaries(options=True)
    assert plain and len(standing(plain, False)) == len(plain)
    assert len(found) == sum(typ.count("[") for _, _, typ in plain), \
        (plain, found)
    for in_loops in (True, False):
        assert sum(map(_sum_bytes, standing(found, in_loops))) <= \
            sum(map(_sum_bytes, standing(plain, in_loops))), (plain, found)
    assert len(standing(found, True)) <= len(standing(plain, True)), \
        (plain, found)
    assert temp < plain_temp * 7 / 6, (plain_temp, temp)
    if "moe_num_experts" in widths:
        # the banks' gradient sums are the bytes: they run under products
        assert sum(is_async for _, is_async, _ in found) >= 8, found

"""What a slot holds besides pages where the stack has a state-space mixer
(``inference/kv_cache.py::RecurrentState``): its shape and bytes; and what
cannot follow it (the prefix cache, the speculative lanes, the host spill
ring, session migration, the int8 plane, the tensor-parallel layout) each
refusing such a stack when the engine is built (or, for migration, when it
is first asked), with one sentence that names the module."""

import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine, migration
from paddle_tpu.inference.kv_cache import PagedKVCache, RecurrentState
from paddle_tpu.inference.kv_spill import HostSpillPool
from paddle_tpu.models.decoder_spec import (DecoderSpec, LatentAttn,
                                            LayerKind, SsmMixer)
from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                         FalconH1ForCausalLM)

GEOMETRY = dict(max_batch=4, max_seq_len=128, page_size=16, prefill_bucket=16)
PUBLISHED = SsmMixer(heads=32, head_dim=128, state=256, groups=2, conv=4)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return FalconH1ForCausalLM(FalconH1Config.tiny())


def test_bytes_a_slot_at_the_published_sizes():
    """4,194,304 B of float32 state and three rows of 5,120 in bf16 a
    layer: 4,225,024; four layers 16,900,096; 128 slots 2.16 GB."""
    assert PUBLISHED.state_bytes("bfloat16") == 4 * 32 * 128 * 256 \
        + 3 * 5120 * 2 == 4_225_024
    assert RecurrentState.bytes_per_slot(PUBLISHED, 4, "bfloat16") == \
        16_900_096
    assert 128 * 16_900_096 == 2_163_212_288
    # a slot's state holds what 2,063 cached tokens of this model's KV hold
    per_token = PagedKVCache.bytes_per_page(4, 4, 16, 128, "bfloat16") // 16
    assert per_token == 8192 and 16_900_096 // per_token == 2063


def test_the_state_rides_with_the_pool_by_slot(model):
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    cache = eng.g.cache
    kv, ssm, conv = cache.arrays
    assert kv.shape == (model.config.num_hidden_layers,
                        cache.allocator.num_pages, 2, cache.num_kv_heads,
                        cache.page_size, cache.head_dim)
    c = model.config
    assert ssm.shape == (c.num_hidden_layers, 4, c.mamba_n_heads,
                         c.mamba_d_head, c.mamba_d_state)
    assert ssm.dtype == jnp.float32                 # whatever the model's
    assert conv.shape == (c.num_hidden_layers, 4, c.mamba_d_conv - 1,
                          c.mamba_d_ssm + 2 * c.mamba_n_groups
                          * c.mamba_d_state)
    assert (ssm.nbytes + conv.nbytes) // 4 == eng.g.state_bytes_per_slot \
        == RecurrentState.bytes_per_slot(cache.recurrent.mixer,
                                         c.num_hidden_layers, "float32")
    # the pool's own bytes do not count it: it is no page
    assert kv.nbytes == eng.g.pool_bytes
    assert eng.step_operands(16)[1][1].shape == ssm.shape


def test_a_stack_states_one_kind_of_state():
    mixed = (LayerKind(ssm=PUBLISHED), LayerKind())
    with pytest.raises(ValueError, match="one recurrent state serves"):
        DecoderSpec(pattern=mixed, periods=2, num_heads=4, num_kv_heads=2,
                    head_dim=32)
    with pytest.raises(ValueError, match="sequential residuals only"):
        DecoderSpec(pattern=(LayerKind(ssm=PUBLISHED),), periods=2,
                    num_heads=4, num_kv_heads=2, head_dim=32,
                    parallel_block=True)
    with pytest.raises(ValueError, match="per-head attention"):
        DecoderSpec(pattern=(LayerKind(
            ssm=PUBLISHED, latent=LatentAttn(128, 64, 64, 64)),), periods=2,
            num_heads=4, num_kv_heads=2, head_dim=32)
    with pytest.raises(ValueError, match="whole number a group"):
        SsmMixer(heads=5, head_dim=16, state=32, groups=2, conv=4)
    plain = DecoderSpec(pattern=(LayerKind(),), periods=2, num_heads=4,
                        num_kv_heads=2, head_dim=32)
    assert plain.ssm is None


def test_the_prefix_cache_refuses_a_recurrent_state(model):
    with pytest.raises(ValueError, match=r"inference/prefix_cache\.py"):
        ContinuousBatchingEngine(model, prefix_cache=True, **GEOMETRY)


@pytest.mark.parametrize("mode", ["ngram", "fused"])
def test_the_speculative_lanes_refuse_a_recurrent_state(model, mode):
    with pytest.raises(ValueError, match=r"inference/speculative\.py"):
        ContinuousBatchingEngine(model, spec_decode=mode, spec_k=4,
                                 **GEOMETRY)


def test_the_spill_ring_refuses_a_recurrent_state(model):
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    with pytest.raises(ValueError, match=r"inference/kv_spill\.py"):
        HostSpillPool(eng.g.cache, 8)


def test_migration_refuses_a_recurrent_state(model):
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    for call in (lambda: migration.warm(eng),
                 lambda: migration.export_session(eng, tokens=[1, 2, 3]),
                 lambda: migration.import_session(eng, {})):
        with pytest.raises(migration.MigrationError,
                           match=r"inference/migration\.py.*recurrent"):
            call()


def test_the_int8_plane_refuses_a_recurrent_state(model):
    with pytest.raises(ValueError, match=r"inference/kv_cache\.py.*int8"):
        ContinuousBatchingEngine(model, cache_dtype="int8", **GEOMETRY)
    with pytest.raises(ValueError, match=r"inference/kv_cache\.py"):
        PagedKVCache(2, 8, 16, 1, 32, dtype="int8",
                     recurrent=RecurrentState(PUBLISHED, 2, 1, "bfloat16"))


def test_tensor_parallel_refuses_a_recurrent_state(model):
    with pytest.raises(ValueError, match="recurrent state"):
        ContinuousBatchingEngine(model, tensor_parallel=2, **GEOMETRY)

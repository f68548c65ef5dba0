"""What stands on the page pool, against a LATENT pool (one row a token a
layer, no head axis): the prefix cache works over the pool's arrays; the
host spill ring, session migration, the int8 plane and the tensor-parallel
layout each refuse it when the engine is built (or, for migration, when it
is first asked), with a sentence that names the module."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine, migration
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.models.sarvam_mla import (SarvamMlaConfig,
                                          SarvamMlaForCausalLM)

GEOMETRY = dict(max_batch=4, max_seq_len=256, page_size=16, prefill_bucket=64)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return SarvamMlaForCausalLM(SarvamMlaConfig.tiny())


def test_the_pool_is_one_row_a_token_a_layer(model):
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    cache = eng.g.cache
    pages = 4 * 16
    assert cache.latent == (128, 64) and cache.page_axis == 1
    c, r = cache.arrays
    assert c.shape == (3, pages, 16, 128)          # no head axis
    assert r.shape == (3, pages, 8, 128)           # two tokens a row
    assert c.nbytes + r.nbytes == eng.g.pool_bytes == \
        pages * PagedKVCache.bytes_per_page(3, 1, 16, 192, "float32",
                                            latent=(128, 64))
    # 512 + 64 in bf16 over five layers: 1,152 B a token a layer, 5,760 all
    assert PagedKVCache.bytes_per_page(5, 1, 16, 192, "bfloat16",
                                       latent=(512, 64)) == 16 * 5760
    assert PagedKVCache.pages_for(32, 16640, 16) == 33280


def test_prefix_cache_shares_and_copies_latent_pages(model):
    """A prompt asked twice: the second admission attaches the cached pages
    (no prefill for them) and privatises its last page copy-on-write over
    BOTH planes of the pool; tokens equal the cache-off engine's."""
    rng = np.random.default_rng(2)
    prompt = list(rng.integers(1, 256, 96))        # six whole pages
    plain = ContinuousBatchingEngine(model, **GEOMETRY)
    want = plain.submit(prompt, max_new_tokens=5)
    want = plain.run()[want.req_id]
    eng = ContinuousBatchingEngine(model, prefix_cache=True, **GEOMETRY)
    first = eng.submit(prompt, max_new_tokens=5)
    assert eng.run()[first.req_id] == want
    again = eng.submit(prompt, max_new_tokens=5)
    other = eng.submit(prompt[:48] + [9] * 20, max_new_tokens=5)
    done = eng.run()
    assert done[again.req_id] == want
    stats = eng.stats()
    assert stats["prefix_hits"] >= 2 and stats["prefix_tokens_saved"] >= 96
    assert stats["cow_copies"] >= 1
    check = ContinuousBatchingEngine(model, **GEOMETRY)
    ref = check.submit(prompt[:48] + [9] * 20, max_new_tokens=5)
    assert done[other.req_id] == check.run()[ref.req_id]


def test_the_spill_ring_refuses_a_latent_pool(model):
    with pytest.raises(ValueError, match=r"inference/kv_spill\.py"):
        ContinuousBatchingEngine(model, prefix_cache=True, kv_spill_pages=8,
                                 **GEOMETRY)


def test_migration_refuses_a_latent_pool(model):
    eng = ContinuousBatchingEngine(model, prefix_cache=True, **GEOMETRY)
    for call in (lambda: migration.warm(eng),
                 lambda: migration.export_session(eng, tokens=[1, 2, 3]),
                 lambda: migration.import_session(eng, {})):
        with pytest.raises(migration.MigrationError,
                           match=r"inference/migration\.py"):
            call()


def test_the_int8_plane_refuses_a_latent_pool(model):
    with pytest.raises(ValueError, match=r"inference/kv_cache\.py.*int8"):
        ContinuousBatchingEngine(model, cache_dtype="int8", **GEOMETRY)


def test_tensor_parallel_refuses_a_latent_pool(model):
    with pytest.raises(ValueError, match="latent"):
        ContinuousBatchingEngine(model, tensor_parallel=2, **GEOMETRY)

"""Multiprocess (fork) DataLoader tests.

Reference behavior: python/paddle/io/reader.py:262 + dataloader/worker.py —
num_workers>0 forks worker processes over shared memory; batch order is
deterministic; worker_init_fn runs per worker; worker errors surface in the
parent.  These tests exercise the mp_loader path directly (it is also the
default path through DataLoader when use_shared_memory=True).
"""

import os
import time

import numpy as np
import pytest

import paddle_tpu.io as io
from paddle_tpu.io.mp_loader import _MPPrefetchIterator, mp_available

pytestmark = pytest.mark.skipif(not mp_available(),
                                reason="fork or native lib unavailable")


class PidDataset(io.Dataset):
    """Sample carries (idx, worker pid, worker id) so the parent can verify
    real multi-process execution and get_worker_info propagation."""

    def __len__(self):
        return 24

    def __getitem__(self, i):
        info = io.get_worker_info()
        wid = -1 if info is None else info.id
        return (np.full((4,), i, dtype=np.int64),
                np.full((1,), os.getpid(), dtype=np.int64),
                np.full((1,), wid, dtype=np.int64))


class FailingDataset(io.Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, i):
        if i == 7:
            raise ValueError("boom at 7")
        return np.full((2,), i, dtype=np.int64)


class SpinDataset(io.Dataset):
    """CPU-bound pure-python transform (GIL-holding): only real processes
    can overlap it.  With ``meet``, a worker's first item does not return
    before ``meet`` workers stand inside the transform at once: a barrier
    that only processes running side by side can pass."""

    def __init__(self, n=12, ms=30, meet=0):
        import multiprocessing as mp
        self.n, self.ms, self.meet = n, ms, meet
        self.arrived = mp.get_context("fork").Value("i", 0)
        self._met = False

    def __len__(self):
        return self.n

    def _spin(self, until):
        acc = 0
        while not until():
            acc += 1  # pure python spin: holds the GIL

    def __getitem__(self, i):
        if self.meet and not self._met and io.get_worker_info() is not None:
            self._met = True
            with self.arrived.get_lock():
                self.arrived.value += 1
            give_up = time.perf_counter() + 60
            self._spin(lambda: self.arrived.value >= self.meet
                       or time.perf_counter() > give_up)
            if self.arrived.value < self.meet:
                raise TimeoutError(
                    f"{self.arrived.value} of {self.meet} workers arrived")
        done = time.perf_counter() + self.ms / 1e3
        self._spin(lambda: time.perf_counter() >= done)
        return (np.full((2,), i, dtype=np.int64),
                np.full((1,), os.getpid(), dtype=np.int64))


def test_order_and_values_match_single_process():
    ds = PidDataset()
    ref = [b for b in io.DataLoader(ds, batch_size=4, shuffle=False,
                                    num_workers=0)]
    got = [b for b in io.DataLoader(ds, batch_size=4, shuffle=False,
                                    num_workers=3)]
    assert len(ref) == len(got)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r[0].numpy(), g[0].numpy())


def test_multiple_processes_actually_used():
    dl = io.DataLoader(PidDataset(), batch_size=2, num_workers=3)
    it = iter(dl)
    assert isinstance(it, _MPPrefetchIterator)
    pids, wids = set(), set()
    for batch in it:
        pids.update(int(p) for p in batch[1].numpy().ravel())
        wids.update(int(w) for w in batch[2].numpy().ravel())
    assert os.getpid() not in pids          # work happened off-parent
    assert len(pids) >= 2                   # on >=2 cores' worth of procs
    assert wids <= {0, 1, 2} and len(wids) >= 2
    assert -1 not in wids                   # get_worker_info set everywhere


def test_worker_init_fn_runs_in_worker():
    seen = []

    def init(wid):
        # runs in the CHILD: mutate the dataset copy there
        PidDataset.tag = wid
        seen.append(wid)  # parent's list is not shared; stays empty here

    dl = io.DataLoader(PidDataset(), batch_size=4, num_workers=2,
                       worker_init_fn=init)
    list(iter(dl))
    assert seen == []  # proves workers are processes, not threads


def test_error_propagates_with_traceback():
    dl = io.DataLoader(FailingDataset(), batch_size=4, num_workers=2)
    with pytest.raises(RuntimeError, match="boom at 7"):
        list(iter(dl))


def test_oversized_batches_take_side_queue():
    class Ragged(io.Dataset):
        """Sample 0 (the slot-sizing probe) is tiny; later samples are huge,
        so their batches overflow the ring into the pickle side queue."""

        def __len__(self):
            return 8

        def __getitem__(self, i):
            n = 4 if i == 0 else 1 << 16
            return np.full((n,), i, dtype=np.int64)

    out = list(io.DataLoader(Ragged(), batch_size=1, shuffle=False,
                             num_workers=2))
    assert len(out) == 8
    for i, b in enumerate(out):
        n = 4 if i == 0 else 1 << 16
        np.testing.assert_array_equal(
            b.numpy(), np.full((1, n), i, dtype=np.int64))


def test_device_tensor_dataset_falls_back_to_threads():
    """A dataset emitting device-backed Tensors must NOT take the fork path
    (device traffic in a forked child can deadlock) — DataLoader silently
    degrades to the thread prefetcher."""
    import paddle_tpu as P
    from paddle_tpu.io import _PrefetchIterator

    class TensorDS(io.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return P.to_tensor(np.full((4,), i, dtype=np.int64))

    it = iter(io.DataLoader(TensorDS(), batch_size=2, num_workers=2))
    assert isinstance(it, _PrefetchIterator)
    out = [b for b in it]
    assert len(out) == 4
    np.testing.assert_array_equal(out[0].numpy(),
                                  np.stack([np.full((4,), 0, np.int64),
                                            np.full((4,), 1, np.int64)]))


def test_cpu_bound_transform_scales_past_one_core():
    """Three workers hold the GIL-bound transform at the same time (each
    waits inside it for the other two), every one of them produces
    batches, and order and content are the sequential loader's.  What a
    CPU can show: how much faster that is belongs to no test."""
    seq = list(io.DataLoader(SpinDataset(), batch_size=1, num_workers=0))
    ds = SpinDataset(meet=3)
    par = list(io.DataLoader(ds, batch_size=1, num_workers=3))
    assert len(seq) == len(par) == 12
    for s, p in zip(seq, par):
        np.testing.assert_array_equal(s[0].numpy(), p[0].numpy())
    assert ds.arrived.value == 3
    pids = {int(b[1].numpy()[0, 0]) for b in par}
    assert len(pids) == 3 and os.getpid() not in pids
    assert {int(b[1].numpy()[0, 0]) for b in seq} == {os.getpid()}


def test_shuffle_epoch_reproducible_single_vs_mp():
    ds = PidDataset()
    sampler = io.BatchSampler(ds, shuffle=True, batch_size=4, drop_last=False)
    ref = [b[0].numpy() for b in io.DataLoader(ds, batch_sampler=sampler,
                                               num_workers=0)]
    # same sampler object: second epoch reshuffles; use fresh equal-seeded one
    sampler2 = io.BatchSampler(ds, shuffle=True, batch_size=4, drop_last=False)
    got = [b[0].numpy() for b in io.DataLoader(ds, batch_sampler=sampler2,
                                               num_workers=2)]
    assert len(ref) == len(got)


def test_prefetch_to_device_passthrough_and_sharded():
    """prefetch_to_device: order/values preserved for pytree batches, and a
    sharded put places the global batch over the mesh (reference analog:
    reader.py places/use_buffer_reader async H2D)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.io import prefetch_to_device

    batches = [{"x": np.full((8, 4), i, np.float32), "i": np.int32(i)}
               for i in range(7)]
    out = list(prefetch_to_device(iter(batches), size=3))
    assert len(out) == 7
    for i, b in enumerate(out):
        assert isinstance(b["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(b["x"]),
                                      np.full((8, 4), i, np.float32))
        assert int(b["i"]) == i

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "mp"))
    sh = NamedSharding(mesh, P("dp", None))
    out = list(prefetch_to_device(iter(batches[:3]), size=2, sharding=sh))
    assert all(b["x"].sharding == sh for b in out)

    # Tensor inputs unwrap to arrays
    import paddle_tpu as paddle
    t = [paddle.to_tensor(np.ones((2, 2), np.float32))]
    (o,) = list(prefetch_to_device(t, size=1))
    assert isinstance(o, jax.Array)


def test_prefetch_to_device_bad_divisibility_raises():
    """A batch dim that doesn't divide the mesh axis must raise at the put
    site, not silently land unsharded; scalar leaves replicate."""
    import jax
    import numpy as np
    import pytest
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.io import prefetch_to_device

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "mp"))
    sh = NamedSharding(mesh, P("dp", None))
    bad = [{"x": np.zeros((7, 4), np.float32)}]   # 7 % 4 != 0
    with pytest.raises(ValueError):
        list(prefetch_to_device(bad, size=1, sharding=sh))

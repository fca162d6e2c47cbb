"""Process-worker DataLoader (round-3 VERDICT item 9).

Parity model: python/mxnet/gluon/data/dataloader.py:50-93 — worker
processes with shared-memory NDArray hand-off. Here workers are
spawned, run dataset[i] + batchify, and return host trees whose numpy
leaves ride POSIX shared memory into the parent."""
import os
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import data as gdata


class SquareDataset(gdata.Dataset):
    """Top-level (picklable) dataset with a python transform."""

    def __init__(self, n=32):
        self._n = n

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        x = onp.full((4, 4), float(i), onp.float32)
        return x * x, onp.int32(i)


class RendezvousDataset(SquareDataset):
    """Labels each sample with the process that loaded it. Sample 0
    (the first batch) is not handed over until ANOTHER process has
    loaded the last sample of the second batch, so one process alone
    can never finish the first batch; the wait is bounded, and a
    sample that gave up says so in its label."""

    def __init__(self, n, batch_size, root):
        super().__init__(n)
        self._last_of_second = 2 * batch_size - 1
        self._root = root

    def __getitem__(self, i):
        x, _ = super().__getitem__(i)
        me = os.getpid()
        met = 0
        if i == self._last_of_second:
            open(os.path.join(self._root, str(me)), "w").close()
        if i == 0:
            deadline = time.monotonic() + 60
            while not met and time.monotonic() < deadline:
                met = int(any(int(f) != me
                              for f in os.listdir(self._root)))
                time.sleep(0.005)
        return x, onp.array([me, met], onp.int32)


def test_process_loader_matches_thread_loader():
    ds = SquareDataset(20)
    thread = gdata.DataLoader(ds, batch_size=4, num_workers=0)
    proc = gdata.DataLoader(ds, batch_size=4, num_workers=2,
                            thread_pool=False)
    got_t = [(d.asnumpy(), l.asnumpy()) for d, l in thread]
    got_p = [(d.asnumpy(), l.asnumpy()) for d, l in proc]
    assert len(got_t) == len(got_p) == 5
    for (dt, lt), (dp, lp) in zip(got_t, got_p):
        onp.testing.assert_allclose(dp, dt)
        onp.testing.assert_array_equal(lp, lt)


def test_process_loader_multiple_epochs_and_shuffle():
    ds = SquareDataset(12)
    proc = gdata.DataLoader(ds, batch_size=3, num_workers=2,
                            thread_pool=False, shuffle=True)
    seen1 = sorted(int(v) for _, l in proc for v in l.asnumpy())
    seen2 = sorted(int(v) for _, l in proc for v in l.asnumpy())
    assert seen1 == seen2 == list(range(12))


@pytest.mark.skipif((os.cpu_count() or 1) < 2,
                    reason="needs >1 core to demonstrate scaling")
def test_process_loader_scales_past_gil(tmp_path):
    """What lets the loader scale past the GIL, by count: the batches
    of one epoch are made in at least two worker processes, none of
    them this one, and the second batch's samples were loaded before
    the first batch was consumed here."""
    ds = RendezvousDataset(24, 4, str(tmp_path))
    proc = gdata.DataLoader(ds, batch_size=4, num_workers=2,
                            thread_pool=False)
    labels = [l.asnumpy() for _, l in proc]
    assert len(labels) == 6
    pids = {int(row[0]) for batch in labels for row in batch}
    assert len(pids) >= 2 and os.getpid() not in pids, pids
    assert labels[0][0][1] == 1, "the first batch waited in vain"


def test_partial_epoch_releases_shared_memory():
    """Breaking out of an epoch must not leak /dev/shm segments
    (review finding, round 4)."""
    import glob
    ds = SquareDataset(32)
    proc = gdata.DataLoader(ds, batch_size=4, num_workers=2,
                            thread_pool=False)
    before = set(glob.glob("/dev/shm/*"))
    it = iter(proc)
    next(it)
    it.close()   # abandon mid-epoch -> finally reaps in-flight shm
    time.sleep(0.5)
    after = set(glob.glob("/dev/shm/psm_*"))  # data segments only —
    # sem.mp-* are the live pool's semaphores, freed with the pool
    leaked = [p for p in after - before]
    assert not leaked, leaked

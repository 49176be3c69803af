"""The one-shot archive path, ``repro.api.open(...).write(sid, x)``, held
to what an archive guarantees: the read-back against a plain numpy
reference, reads beside a write on the same store, and the write's spans.
"""
from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np
import pytest

import repro.api as api
from repro import obs
from repro.core.cameo import CameoConfig
from repro.data.synthetic import make_dataset

CFG = CameoConfig(eps=0.01, lags=48, kappa=1, stat="acf", measure="mae",
                  dtype="float64")


def acf_np(y, L):
    """Per lag ``l``, the Pearson correlation of ``y[:-l]`` with ``y[l:]``."""
    out = np.zeros(L)
    for l in range(1, L + 1):
        a, b = y[:-l] - y[:-l].mean(), y[l:] - y[l:].mean()
        out[l - 1] = np.dot(a, b) / np.sqrt(np.dot(a, a) * np.dot(b, b))
    return out


@pytest.fixture
def telemetry():
    was = obs.enabled()
    obs.reset()
    yield obs.OBS
    obs.reset()
    obs.OBS.enabled = was


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_write_reads_back_as_numpy_says(tmp_path, seed):
    x = make_dataset("uk_elec", seed=seed, length=2048)
    with api.open(str(tmp_path / "a.cameo"), CFG, mode="w") as ds:
        entry = ds.write("s", x)
        ser = ds.series("s")
        idx, vals = ser.kept()
        xr = ser.window()
    assert ser.n == len(x) and idx[0] == 0 and idx[-1] == len(x) - 1
    # kept values are the written points, bit for bit
    assert np.array_equal(vals, x[idx])
    # the decode is the straight lines between them, to the rounding of
    # the interpolation formula (an ulp: the benchmark's interp_gap limit)
    lines = np.interp(np.arange(len(x), dtype=np.float64),
                      idx.astype(np.float64), vals)
    assert np.max(np.abs(xr - lines)) <= 1e-11 * np.max(np.abs(x))
    dev = float(np.mean(np.abs(acf_np(xr, CFG.lags) - acf_np(x, CFG.lags))))
    assert dev <= CFG.eps
    assert abs(float(entry["deviation"]) - dev) <= 1e-9 * CFG.eps


@pytest.mark.parametrize("with_resid", [True, False])
@pytest.mark.parametrize("channels", [1, 2])
def test_stored_kept_values_are_the_written_points(tmp_path, channels,
                                                   with_resid):
    """A result whose reconstruction is an ulp off at every point, kept
    ones included (as the device's emulated float64 can be): the store
    keeps the written points, whether or not it keeps residuals."""
    from types import SimpleNamespace

    from repro.store.store import CameoStore
    x = make_dataset("uk_elec", seed=5, length=1024)
    X = x if channels == 1 else np.stack([x, x[::-1]], axis=1)
    kept = np.zeros(len(x), bool)
    kept[::7] = kept[-1] = True
    res = SimpleNamespace(kept=kept, xr=np.nextafter(X, np.inf),
                          deviation=0.0)
    with CameoStore.create(str(tmp_path / "a.cameo"), block_len=256) as st:
        entry = st.append_series("s", res, CFG, x=X, with_resid=with_resid)
        idx, vals = st.read_kept("s")
    assert entry["has_resid"] is with_resid
    np.testing.assert_array_equal(idx, np.nonzero(kept)[0])
    assert np.array_equal(vals, X[idx])


class _Yielding:
    """A store's file that hands the interpreter to another thread after
    every seek, so that whatever another thread does with the file can
    fall between a seek and the read or write that follows it."""

    def __init__(self, f):
        self._f = f

    def seek(self, *a):
        out = self._f.seek(*a)
        time.sleep(0)
        return out

    def __getattr__(self, name):
        return getattr(self._f, name)


@pytest.mark.parametrize("mmap", ["1", "0"])
def test_reads_beside_writes_answer_as_alone(tmp_path, monkeypatch, mmap):
    """Two threads read a stored series (every read fetches block bodies:
    no block cache) while another writes 20 more to the same store; every
    answer equals the one a read alone gives."""
    monkeypatch.setenv("CAMEO_MMAP", mmap)
    cfg = CameoConfig(eps=0.01, lags=8, dtype="float64")
    x = make_dataset("uk_elec", seed=7, length=2048)
    more = [make_dataset("uk_elec", seed=100 + i, length=1024)
            for i in range(20)]
    with api.open(str(tmp_path / "a.cameo"), cfg, mode="w", block_len=64,
                  cache_bytes=0) as ds:
        ds.write("base", x)
        ds.store._f = _Yielding(ds.store._f)
        ser = ds.series("base")

        def answers():
            return [ser.window(), *ser.kept(), *ser.mean(100, 1900),
                    *ser.acf(0, 2048)]
        alone = answers()
        done = threading.Event()
        seen, errors = [], []

        def reader():
            try:
                while not done.is_set():
                    seen.append(answers())
            except Exception as e:      # reported below, with the answers
                errors.append(e)

        readers = [threading.Thread(target=reader) for _ in range(2)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for th in readers:
                th.start()
            for i, xi in enumerate(more):
                ds.write(f"more{i}", xi)
        finally:
            done.set()
            for th in readers:
                th.join(timeout=60)
            sys.setswitchinterval(interval)
        assert not any(th.is_alive() for th in readers)
        assert not errors, errors
        assert seen
        for got in seen:
            for a, b in zip(got, alone):
                np.testing.assert_array_equal(a, b)
        for i, xi in enumerate(more):
            idx, vals = ds.series(f"more{i}").kept()
            assert np.array_equal(vals, xi[idx])


@pytest.mark.parametrize("mmap", ["1", "0"])
def test_reads_do_not_wait_out_the_fsync(tmp_path, monkeypatch, mmap):
    """A block read on another thread completes while the writer's footer
    sits in its fsync barrier: the store's I/O lock covers the writes, not
    the barrier."""
    monkeypatch.setenv("CAMEO_MMAP", mmap)
    monkeypatch.setenv("CAMEO_FSYNC", "1")
    cfg = CameoConfig(eps=0.01, lags=8, dtype="float64")
    x = make_dataset("uk_elec", seed=7, length=1024)
    in_barrier, read_done = threading.Event(), threading.Event()
    overlapped, got = [], []
    fsync = os.fsync

    def slow_fsync(fd):
        in_barrier.set()
        overlapped.append(read_done.wait(timeout=10))
        fsync(fd)

    with api.open(str(tmp_path / "a.cameo"), cfg, mode="w", block_len=64,
                  cache_bytes=0) as ds:
        ds.write("base", x)
        ser = ds.series("base")
        alone = ser.window()

        def reader():
            in_barrier.wait(timeout=10)
            got.append(ser.window())
            read_done.set()

        th = threading.Thread(target=reader)
        th.start()
        monkeypatch.setattr(os, "fsync", slow_fsync)
        try:
            ds.store.flush()
        finally:
            monkeypatch.setattr(os, "fsync", fsync)
            th.join(timeout=30)
    assert overlapped and overlapped[0]
    np.testing.assert_array_equal(got[0], alone)


def test_write_spans(tmp_path, telemetry):
    new = ("dataset.write", "dataset.write.compress", "store.append_series")
    assert set(new) <= set(obs.SPANS)
    obs.enable()
    x = make_dataset("uk_elec", seed=3, length=1024)
    with api.open(str(tmp_path / "a.cameo"),
                  CameoConfig(eps=0.01, lags=8), mode="w") as ds:
        ds.write("s", x)
    snap = obs.snapshot()
    for name in new:
        assert snap["counters"][f"span.{name}.calls"] == 1, name
    h = snap["histograms"]
    whole = h["span.dataset.write.seconds"]["sum"]
    assert h["span.dataset.write.compress.seconds"]["sum"] <= whole
    assert h["span.store.append_series.seconds"]["sum"] <= whole

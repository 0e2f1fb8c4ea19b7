"""Checks of the benchmark itself, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

- the copied generators give exactly the program's gallery matrices;
- the trace reduction, on a trace recorded on a v5e
  (``data/tiny_trace.xplane.pb``, made by ``record_trace.py``);
- a run of each cell's path, on a smaller copy of its configuration (the
  matrix of its ``test_matrix`` key) and with the look for a chip skipped,
  is correct; the control of
  ``correct`` (the program's single-precision path) and each fault that a
  one-chip solve can have, planted underneath the harness, are not.
"""

import copy
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import matrices, readings, run, spec, trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("generator,params,gallery", [
    ("poisson3d", {"nx": 6}, lambda g: g.poisson3d(6)),
    ("convdiff2d", {"nx": 9, "beta": 10.0},
     lambda g: g.convection_diffusion_2d(9, beta=10.0)),
])
def test_generator_matches_gallery(generator, params, gallery):
    from superlu_dist_tpu.models import gallery as g
    m, a = matrices.build(generator, params), gallery(g)
    assert m.n == a.n_rows == a.n_cols
    np.testing.assert_array_equal(m.indptr, a.indptr)
    np.testing.assert_array_equal(m.indices, a.indices)
    np.testing.assert_array_equal(m.data, a.data)
    assert m.grid_shape == tuple(a.grid_shape)


def test_config_sizes_match_their_matrices():
    for cell in CELLS:
        cfg = spec.load_cell(cell).config
        m = matrices.build(cfg["generator"], cfg["matrix"])
        assert (m.n, m.nnz) == (cfg["n"], cfg["nnz"])


def test_union_and_gaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]
    host = [("outer", 0, 100), ("inner", 10, 40), ("other", 38, 60)]
    assert trace_reduce._label((12, 30), host) == "inner"
    assert trace_reduce._label((45, 55), host) == "other"
    assert trace_reduce._label((200, 300), host) == "(no host event)"


def test_reduce_recorded_trace():
    """Trace of record_trace.py, recorded on one v5e: three 50 ms sleeps
    inside ``rhs_gen``, each followed by 20 matmuls inside ``gssvx``."""
    path = os.path.join(DATA, "tiny_trace.xplane.pb")
    r = trace_reduce.reduce(path, n_devices=1)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_share_pct"] == pytest.approx(
        100 * (1 - r["busy_s"] / r["window_s"]))
    # busy is a union: no more than the summed op time, and no less
    # than the longest op
    total = sum(t for _, t in r["device_ops"])
    assert r["busy_s"] <= total + 1e-9
    assert r["busy_s"] >= max(t for _, t in r["device_ops"])
    # the host slept 3 x 50 ms with the device idle
    assert r["window_s"] - r["busy_s"] >= 0.15
    gaps = r["idle_gaps"]
    assert [name for name, _ in gaps[:3]] == ["rhs_gen"] * 3
    assert all(0.045 < t < 0.2 for _, t in gaps[:3])
    assert gaps[3][1] < 0.01
    assert r["device_ops"][0][0].startswith("jit__lambda:")
    assert r["idle_gaps"] == sorted(r["idle_gaps"], key=lambda g: -g[1])
    assert r["device_ops"][0][1] == max(t for _, t in r["device_ops"])


def small_cell(name):
    cell = spec.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["matrix"].update(cell.config["test_matrix"])
    return cell


def drive(name, gssvx=None, seed=2**33 + 7):
    return run.run(name, seed, 0.5, False, require_tpu=False,
                   cell=small_cell(name), gssvx=gssvx)


def broken(fault):
    """gssvx in the control's precision, or with a fault planted where
    its answer is produced."""
    import superlu_dist_tpu as slu
    last = {}

    if fault == "control":
        return lambda options, a, b, lu=None, **kw: slu.gssvx(
            run.control_options(options), a, b, lu=lu, **kw)

    def gssvx(options, a, b, lu=None, **kw):
        x, lu, stats, info = slu.gssvx(options, a, b, lu=lu, **kw)
        if fault == "stale":          # state returned unchanged
            x, last["x"] = last.get("x", np.zeros_like(x)), x
        elif fault == "half":           # half of the answer left out
            x = x.copy()
            x[len(x) // 2:] = 0.0
        elif fault == "altered":        # one entry altered where produced
            x = x.copy()
            i = int(np.argmax(np.abs(x)))
            x[i] *= 1.0 + 1e-6
        elif fault == "singular" and last.setdefault("calls", 0):
            x, info = None, 1           # after set-up: a zero pivot
        last["calls"] = last.get("calls", 0) + 1
        return x, lu, stats, info

    return gssvx


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = drive(name)
    assert out["correct"], out
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in
                                   spec.load_cell(name).end_to_end}


@pytest.mark.parametrize("fault", ["control", "stale", "half", "altered",
                                   "singular"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_run_is_not_correct(name, fault):
    out = drive(name, gssvx=broken(fault))
    assert not out["correct"], out
    assert out["failed"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_readings_separate_program_and_control(name):
    rows = list(readings.readings(small_cell(name), [11, 12], [13], 0.2,
                                  require_tpu=False))
    limits = spec.load_cell(name).config["check"]
    for r in rows:
        over = any(r[k] > limits[k] for k in limits)
        assert over == (r["mode"] == "control"), r


def test_no_tpu_no_result():
    with pytest.raises(SystemExit):
        run.Session(small_cell(CELLS[0]), require_tpu=True)


def test_peaks_unknown_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


@pytest.mark.parametrize("name", CELLS)
def test_shift_sets_equal_across_seeds(name):
    cell = small_cell(name)
    m = matrices.build(cell.config["generator"], cell.config["matrix"])
    from benchmark.traffic import Mix
    a, b = Mix(cell.traffic, m, 1), Mix(cell.traffic, m, 2**40 + 3)
    assert sorted(a.shifts) == sorted(b.shifts)
    assert not np.array_equal(a.shifts, b.shifts)
    np.testing.assert_array_equal(a.request(5).x_true, a.request(5).x_true)

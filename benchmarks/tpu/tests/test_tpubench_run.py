"""Whole runs of the harness on the CPU at tiny sizes.  The look for a
chip is skipped (``require_tpu=False``); everything else runs as on the
chip: set-up, warm-up, the open-loop window and the check.  A sound run
is correct; the control and each fault planted under the timed path
make ``correct`` come out false."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from tpubench_testutil import BENCH, CELLS, ROOT, tiny_cell

from tpubench import harness

SEED = 2_147_483_659        # over 32 signed bits, as the driver's are


def _run(name, *, seconds=2.0, rate=6.0, control=False, fault=None,
         drain_s=20.0, trace=False):
    return harness.run(name, SEED, seconds, trace, time.monotonic(),
                       require_tpu=False, cell=tiny_cell(name),
                       control=control, rate=rate, drain_s=drain_s,
                       before_window=fault)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, capsys):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {m["name"] for m in tiny_cell(name).end_to_end} == \
        set(res["metrics"])
    assert res["device"]["platform"] == "cpu"
    assert res["checks"]["wrong_answers"]["checked"] > 0
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ") and " limit " in err[-1]


@pytest.mark.parametrize("name", CELLS)
def test_cached_answers_are_served_and_checked(name, capsys):
    cell = tiny_cell(name)
    cell.mix["cached"] = {"count": 2}
    res = harness.run(name, SEED, 2.0, False, time.monotonic(),
                      require_tpu=False, cell=cell, rate=6.0, drain_s=20.0)
    assert res["correct"], res["checks"]
    assert "(2 from the result cache)" in capsys.readouterr().err


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    res = _run(name, control=True, rate=12.0)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def _stuck_step(monkeypatch):
    """A tick that returns every slot's state unchanged."""
    from repro.core import dense
    monkeypatch.setattr(dense.DenseStepper, "step", lambda self: True)


def _half_batch(monkeypatch):
    """The chunk program leaves out the second half of its rows."""
    from repro.core import dense
    orig = dense._bfs_chunk_hetero

    def half(subj, pred, obj, B, PRED, front, vis, *args, **kw):
        f, v, it = orig(subj, pred, obj, B, PRED, front, vis, *args, **kw)
        h = f.shape[0] // 2
        return f.at[h:].set(0), v.at[h:].set(vis[h:]), it
    monkeypatch.setattr(dense, "_bfs_chunk_hetero", half)


def _altered_answer(monkeypatch):
    """Each answer loses its largest pair where it is produced."""
    from repro.core.scheduler import SlotScheduler
    orig = SlotScheduler._finish

    def finish(self, ticket, out, key, footprint):
        out = set(out)
        if out:
            out.discard(max(out))
        return orig(self, ticket, out, key, footprint)
    monkeypatch.setattr(SlotScheduler, "_finish", finish)


def _shed_at_the_door(monkeypatch):
    """Admission sheds every request beyond one waiting."""
    from repro.core.scheduler import SlotScheduler
    orig = SlotScheduler.submit

    def submit(self, *args, **kw):
        self.max_queue = 1
        return orig(self, *args, **kw)
    monkeypatch.setattr(SlotScheduler, "submit", submit)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_stuck_step, _half_batch,
                                   _altered_answer, _shed_at_the_door],
                         ids=["state-unchanged", "half-batch",
                              "answer-altered", "shed-at-the-door"])
def test_fault_makes_run_incorrect(name, fault, monkeypatch):
    # bursts of arrivals keep several slots busy at once, so a fault in
    # the batch's second half has rows to land on
    res = _run(name, rate=150.0, seconds=1.0, drain_s=3.0,
               fault=lambda: fault(monkeypatch))
    assert not res["correct"], res["checks"]


def test_traced_run_reports_per_layer_metrics():
    res = _run("kg-steady", trace=True)
    assert res["correct"]
    assert "gen_late_p90_ms" in res["metrics"]
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert res["device"]["window_s"] > 0
    assert "p50_ms" not in res["metrics"]


def _command(cwd, env):
    return subprocess.run(
        [sys.executable, "benchmarks/tpu/run.py", "--workload", "kg-steady",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_tpu():
    r = _command(ROOT, {**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip()


def test_command_fails_with_the_benchmark_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "tpu",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _command(tmp_path, {**env, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert not r.stdout.strip()
    with pytest.raises(json.JSONDecodeError):
        json.loads(r.stdout or "x")


def test_peak_memory_counts_reserved_temporaries():
    stats = {"peak_bytes_in_use": 496_326_144,
             "peak_bytes_reserved": 10_195_877_888}
    assert harness._peak_bytes(stats) == 10_692_204_032
    assert harness._peak_bytes({}) == 0

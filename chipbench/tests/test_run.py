"""The harness end to end on the CPU at a tiny size: the chip check,
discovery by name, a whole run of each driver, and ``correct`` coming
out false when the timed path is broken underneath."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import run as R
from chipbench.tests import tiny

ROOT = tiny.ROOT


def run_tiny(tmp_path, cell, *, trace=0, root=None, **kw):
    root = root or tiny.make_root(tmp_path)
    return R.run_cell(tiny.Args(cell, trace=trace, **kw), root=root,
                      require_tpu=False)


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "vgg19.camera.switch_b1", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert _no_result(p.stdout) and "no TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout)


@pytest.mark.parametrize("cell,trace", [
    ("tiny-vgg.camera", 0), ("tiny-vgg.camera", 1),
    ("tiny-qwen.chat", 0), ("tiny-qwen.chat", 1)])
def test_tiny_cell_runs_correct(tmp_path, cell, trace):
    out = run_tiny(tmp_path, cell, trace=trace, seed=2 ** 33 + 3)
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert out["info"]["compile_cache_misses_in_window"] == 0
    names = set(res["metrics"])
    if trace:
        assert "build_ms" in names
        assert res["device"]["window_s"] > 0
    else:
        assert {"setup_s", "downtime_ms"} <= names


def _digest(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    root = tiny.make_root(tmp_path)
    before = _digest(root / "chipbench")
    cfg = dict(tiny.VGG, name="tiny-vgg-wide",
               layers=[["conv", 12], ["pool"], ["flatten"], ["dense", 10]],
               serving=dict(tiny.VGG["serving"],
                            split_for_mbps={"20": 1, "5": 2}))
    (root / "chipbench/configs/tiny-vgg-wide.json").write_text(
        json.dumps(cfg))
    (root / "chipbench/traffic/tiny.slowcam.json").write_text(
        json.dumps(dict(tiny.CAMERA, arrivals={"process": "poisson",
                                               "rate": 6.0})))
    (root / "chipbench/metrics/frames_per_s.py").write_text(
        "def read(run):\n"
        "    n = sum(r.served for c in run.cycles"
        " for r in c['timeline'].records)\n"
        "    return n / run.window_s\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-vgg-wide", "source": "test",
                             "file": "chipbench/configs/tiny-vgg-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "wide.slowcam", "config":
                               "tiny-vgg-wide", "traffic": "tiny.slowcam",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "frames_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["wide.slowcam"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digest(root / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before
    out = run_tiny(tmp_path, "wide.slowcam", root=root)
    assert out["result"]["correct"] is True
    assert out["result"]["metrics"]["frames_per_s"]["value"] > 0


# -- faults: the timed path broken underneath ------------------------------

def _break_token(monkeypatch):
    """A served token altered where it is produced."""
    from repro.serving.sessions import SessionManager
    orig = SessionManager.next_token

    def next_token(self):
        tok = np.asarray(orig(self)).copy()
        tok[0, 0] = (tok[0, 0] + 1) % self.cfg.vocab_size
        return tok
    monkeypatch.setattr(SessionManager, "next_token", next_token)


def _stale_state(monkeypatch):
    """A decode step that returns its state unchanged."""
    from repro.serving.sessions import SessionManager
    orig = SessionManager.commit_step

    def commit_step(self, token, new_state, bounds, logits):
        orig(self, token, {k: self.cache[k] for k in new_state}, bounds,
             logits)
    monkeypatch.setattr(SessionManager, "commit_step", commit_step)


def _half_batch(monkeypatch):
    """Half of the slots left out of a step: they are served the other
    half's logits.  (Keeping their old logits is no fault a random-weight
    model always shows: its greedy output soon repeats one token.)"""
    from repro.serving.sessions import SessionManager
    orig = SessionManager.commit_step

    def commit_step(self, token, new_state, bounds, logits):
        lg = np.asarray(logits).copy()
        half = self.num_slots // 2
        lg[half:2 * half] = lg[:half]
        orig(self, token, new_state, bounds, lg)
    monkeypatch.setattr(SessionManager, "commit_step", commit_step)


def _bad_frame(monkeypatch):
    """A served frame's answer altered where it is produced."""
    from repro.core.pipeline import EdgeCloudPipeline
    orig = EdgeCloudPipeline.process

    def process(self, inputs, **kw):
        logits, timing = orig(self, inputs, **kw)
        return logits.at[0, 0].add(1.0), timing
    monkeypatch.setattr(EdgeCloudPipeline, "process", process)


# A tiny decoder with a vocabulary and depth at which bf16 rounding moves
# the greedy token at some of the few hundred positions checked, and
# answers long enough for a broken step to move many tokens.
CONTROL_QWEN = dict(tiny.QWEN, vocab_size=8192, num_hidden_layers=8,
                    serving=dict(tiny.QWEN["serving"], num_slots=4,
                                 max_seq=128))
CONTROL_CHAT = dict(tiny.CHAT, population=16,
                    prompt={"median": 16, "sigma": 0.5, "min": 4, "max": 48},
                    answer={"median": 48, "sigma": 0.3, "min": 24, "max": 72})


@pytest.mark.parametrize("cell,fault", [
    ("tiny-qwen.chat", _break_token), ("tiny-qwen.chat", _stale_state),
    ("tiny-qwen.chat", _half_batch), ("tiny-vgg.camera", _bad_frame)])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, cell,
                                          fault):
    fault(monkeypatch)
    # a few cycles, so that sessions of every slot finish however slowly
    # the host runs; the larger tiny decoder, whose answers are long
    # enough for a fault to move many tokens
    root = tiny.make_root(tmp_path, configs={"tiny-vgg": tiny.VGG,
                                             "tiny-qwen": CONTROL_QWEN},
                          traffic={"tiny.camera": tiny.CAMERA,
                                   "tiny.chat": CONTROL_CHAT})
    out = run_tiny(tmp_path, cell, seed=11, seconds=6.0, root=root)
    assert out["result"]["correct"] is False, out["result"]["checks"]


def _control_run(tmp_path, cell, seed, seconds=4.0, **kw):
    from chipbench import control
    root = tiny.make_root(tmp_path, **kw)
    return R.run_cell(tiny.Args(cell, seed=seed, seconds=seconds), root=root,
                      require_tpu=False, after=control.control_in_place)


def test_control_in_the_programs_place_reads_above_the_program_cnn(tmp_path):
    """The CNN's control (the reference at three bf16 passes, written out
    on the CPU) served in the program's place reads far above the
    program.  At this size it stays under the full-size limit, which is
    set from chip readings of ``control.py``, where the control's run
    comes out not correct (PERF.md)."""
    out = _control_run(tmp_path, "tiny-vgg.camera", 4)
    assert out["result"]["correct"] is True, out["result"]["checks"]
    prog = out["result"]["checks"]["logits_rel_err_max"]["value"]
    ctl = out["extra"]["control_checks"]["logits_rel_err_max"]["value"]
    assert ctl > 10 * prog and ctl > 1e-6, out["extra"]


def test_control_in_the_programs_place_reads_above_the_program_lm(tmp_path):
    """The LM's control (the reference in bf16 weights and activations)
    served in the program's place: the program on the CPU computes in
    float32 and reads no gap; the control reads one.  At this size its
    reading is far below the full-size limit, which is set from chip
    readings of ``control.py``, where the control's run comes out not
    correct (PERF.md)."""
    # a longer window: a few hundred checked tokens however slowly the
    # host runs
    out = _control_run(tmp_path, "tiny-qwen.chat", 9, seconds=10.0,
                       configs={"tiny-qwen": CONTROL_QWEN},
                       traffic={"tiny.chat": CONTROL_CHAT})
    assert out["result"]["correct"] is True, out["result"]["checks"]
    prog = out["result"]["checks"]["logit_gap_max"]["value"]
    ctl = out["extra"]["control_checks"]["logit_gap_max"]["value"]
    assert prog == 0.0 and ctl > 0.0, out["extra"]
    assert out["extra"]["control_checks"]["served_mismatch_share"]["value"] > 0

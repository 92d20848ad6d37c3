"""The traffic generator: per-seed determinism, the same work for every
seed, and arrival processes equal to the program's."""
import numpy as np
import pytest

from chipbench import arrivals
from chipbench import traffic as T
from chipbench.tests import tiny

SEED = 2 ** 33 + 17


def test_frames_are_deterministic_per_seed():
    a = T.frame_plan(tiny.CAMERA, SEED, 3)
    assert a == T.frame_plan(tiny.CAMERA, SEED, 3)
    assert a != T.frame_plan(tiny.CAMERA, SEED + 1, 3)
    assert [t for t, _ in a] == [t for t, _ in T.frame_plan(tiny.CAMERA, 1, 0)]


def test_sessions_same_sizes_other_tokens():
    a = T.session_specs(tiny.CHAT, SEED, 256)
    b = T.session_specs(tiny.CHAT, SEED, 256)
    c = T.session_specs(tiny.CHAT, SEED + 1, 256)
    assert all((x[0] == y[0]).all() and x[1] == y[1] for x, y in zip(a, b))
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in c]
    assert any((x[0] != y[0]).any() for x, y in zip(a, c))
    lo, hi = tiny.CHAT["prompt"]["min"], tiny.CHAT["prompt"]["max"]
    assert all(lo <= len(p) <= hi for p, _ in a)


def test_lognormal_quantiles_median_and_clip():
    q = T.lognormal_quantiles({"median": 128, "sigma": 0.8, "min": 16,
                               "max": 256}, 64)
    assert q == sorted(q) and q[0] >= 16 and q[-1] == 256
    assert np.median(q) == pytest.approx(128, rel=0.05)


def test_every_prefix_holds_the_mix_of_lengths():
    chat = dict(tiny.CHAT, population=64)
    answers = [n for _, n in T.session_specs(chat, SEED, 256)]
    mean = np.mean(answers)
    for k in (8, 16, 32):
        assert np.mean(answers[:k]) == pytest.approx(mean, rel=0.15)


def test_link_steps_need_a_split_per_bandwidth():
    steps = T.link_steps(tiny.CHAT, {"20": 3, "5": 1})
    assert steps == [(0.0, 20.0, 3), (1.0, 5.0, 1)]
    with pytest.raises(KeyError):
        T.link_steps(tiny.CHAT, {"20": 3})


@pytest.mark.parametrize("spec", [
    {"process": "uniform", "rate": 30.0},
    {"process": "poisson", "rate": 7.0},
    {"process": "bursty", "rate_on": 40.0, "rate_off": 1.0, "mean_on": 0.5,
     "mean_off": 1.0}])
def test_arrivals_equal_the_programs_generators(spec):
    from repro.serving.workload import get_arrival
    params = {k: v for k, v in spec.items() if k != "process"}
    prog = get_arrival(spec["process"], **params)
    assert arrivals.times(spec, 20.0, seed=5) == \
        list(prog.times(20.0, seed=5))

"""The Zamba2 hybrid on the CPU at a tiny size: the program against the
plain reference through the slot pool and a ``switch_b2`` hand-off, the
whole cell through the harness, and the driver, weights and work counts
tied to the configuration file."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as R
from chipbench import weights_hybrid, work_hybrid
from chipbench.drivers import lm_hybrid
from chipbench.reference import zamba2
from chipbench.tests import tiny

ZAMBA_FILE = tiny.ROOT / "chipbench/configs/zamba2-7b.json"

# the published structure at tiny widths: two groups, two alternating
# blocks, adapters, applications before layers 1 and 3
ZAMBA = dict(json.loads(ZAMBA_FILE.read_text()), **{
    "name": "tiny-zamba", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "attention_head_dim": 32, "attention_hidden_size": 128,
    "mamba_headdim": 16, "mamba_d_state": 8, "adapter_rank": 8,
    "num_hidden_layers": 4, "hybrid_layer_ids": [1, 3, 7],
    "vocab_size": 256,
    "serving": {"dtype": "float32", "precision": "default", "num_slots": 2,
                "max_seq": 64, "decode_impl": "reference",
                "split_for_mbps": {"20": 3, "5": 1}}})


def _program(cfg, seed, *, decode_impl="reference", num_slots=2,
             max_seq=64):
    import dataclasses

    from repro.configs import get_config
    from repro.core.stateful import StatefulStageRunner
    from repro.serving.sessions import SessionManager
    pcfg = dataclasses.replace(get_config("zamba2-7b"),
                               **lm_hybrid.hybrid_values(cfg))
    pcfg = dataclasses.replace(pcfg, ssm=dataclasses.replace(
        pcfg.ssm, **lm_hybrid.ssm_values(cfg)))
    params = weights_hybrid.hybrid_params(cfg, seed)
    runner = StatefulStageRunner(pcfg, params, max_seq=max_seq,
                                 decode_impl=decode_impl)
    return params, SessionManager(runner, num_slots=num_slots)


@pytest.mark.parametrize("mode", ["transfer", "recompute"])
def test_served_logits_match_reference_across_switch(mode):
    """Admission, decode through the slot pool at split 3, a ``switch_b2``
    to split 1 (layers 1-2 change sides: conv/SSM state, the KV of the
    application before layer 1, and the x0 stream at the boundary), more
    decode: every served step's logits agree with the reference's full
    forward over the same tokens."""
    from repro.core import NetworkModel, PipelineManager
    from repro.core.stateful import StatefulPipelinePool
    cfg = dict(ZAMBA, hybrid_layer_ids=[1, 3])
    params, sm = _program(cfg, seed=5)
    net = NetworkModel(20.0, latency_ms=1.0)
    pool = StatefulPipelinePool(sm.runner, net, {"tokens": None},
                                session=sm, force_mode=mode)
    mgr = PipelineManager(sm.runner, 3, net, {"tokens": None}, pool=pool)
    rng = np.random.default_rng(0)
    sids = [sm.admit(rng.integers(0, cfg["vocab_size"], n)) for n in (5, 9)]
    logits = {s: [sm.logits_for(s)] for s in sids}
    for step in range(6):
        if step == 3:
            rep = mgr.repartition("switch_b2", 1)
            assert rep.handoff_mode == mode
        mgr.serve({})
        for s in sids:
            logits[s].append(sm.logits_for(s))
    h = mgr.pool.handoffs[-1]
    assert h.moved_layers == 2
    for s in sids:
        toks = sm.tokens_for(s)
        hid = zamba2.hidden(cfg, params, toks[None])[0]
        ref = np.asarray(jnp.matmul(hid, params["embed"].T,
                                    precision="highest"))
        got = np.stack(logits[s])                 # after each token
        want = ref[len(toks) - len(got):]
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)
    mgr.close()


def test_transfer_moves_both_kinds_of_state():
    """A hybrid hand-off's payload holds the conv/SSM state of the moved
    layers and the KV of the application among them, each kind under its
    own spans and counter."""
    from repro.core import timing
    cfg = dict(ZAMBA, hybrid_layer_ids=[1, 3])
    _, sm = _program(cfg, seed=1)
    sm.admit(np.arange(7) % cfg["vocab_size"])
    timing.tracing(True)
    try:
        timing.clear()
        payload, n = sm.export_layers(1, 3)
        sm.import_layers(payload)
        recs = {r.name: r for r in timing.records()}
    finally:
        timing.tracing(False)
        timing.clear()
    assert {"conv1", "ssm1", "conv2", "ssm2", "ak0", "av0"} \
        == set(payload) - {"__meta__"}
    ssm = recs["handoff.export.ssm"].attrs["handoff_bytes.ssm"]
    kv = recs["handoff.export.kv"].attrs["handoff_bytes.kv"]
    assert ssm > 0 and kv > 0 and ssm + kv == n
    assert {"handoff.import.ssm", "handoff.import.kv"} <= set(recs)


def _root(tmp_path):
    root = tiny.make_root(tmp_path, configs={"tiny-qwen": tiny.QWEN,
                                             "tiny-zamba": ZAMBA})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-zamba.chat",
                               "config": "tiny-zamba",
                               "traffic": "tiny.chat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-qwen.chat" in m.get("workloads", []) or \
                m["name"].endswith(".hybrid") or m["name"] == "ssd_scan_roofline":
            m["workloads"] = m["workloads"] + ["tiny-zamba.chat"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_hybrid_cell_runs_correct(tmp_path, trace):
    out = R.run_cell(tiny.Args("tiny-zamba.chat", trace=trace,
                               seed=2 ** 33 + 5),
                     root=_root(tmp_path), require_tpu=False)
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert out["info"]["compile_cache_misses_in_window"] == 0
    assert out["info"]["checked_crossed_switch"] > 0
    names = set(res["metrics"])
    if trace:
        assert {"build_ms", "handoff_ms"} <= names
        # every repartition handed off both kinds of state
        moved = out["info"]["handoff_bytes"]
        assert moved["ssm"] and all(n > 0 for n in moved["ssm"])
        assert moved["kv"] and all(n > 0 for n in moved["kv"])
    else:
        assert {"setup_s", "downtime_ms", "decode_tok_per_s"} <= names


def test_driver_weights_and_work_follow_the_config_file():
    """The driver gives the program the file's numbers, the weights have
    the program's layout and the file's widths, and the work counts
    count every weight of a decode step once."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = json.loads(ZAMBA_FILE.read_text())
    assert cfg["driver"] == "lm_hybrid"
    vals = lm_hybrid.hybrid_values(cfg)
    assert vals["hybrid_layer_ids"] == (6, 11, 17, 23)
    assert vals["head_dim"] * vals["num_heads"] \
        == cfg["attention_hidden_size"]
    pcfg = dataclasses.replace(get_config("zamba2-7b"), **vals)
    pcfg = dataclasses.replace(pcfg, ssm=dataclasses.replace(
        pcfg.ssm, **lm_hybrid.ssm_values(cfg)))
    assert pcfg.d_inner // pcfg.ssm.head_dim == cfg["n_mamba_heads"]
    assert pcfg.d_inner + 2 * pcfg.ssm.n_groups * pcfg.ssm.d_state == 7424
    made = jax.eval_shape(lambda: weights_hybrid.hybrid_params(cfg, 0))
    prog = jax.eval_shape(lambda: T.init_model(pcfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(made) == jax.tree.structure(prog)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(made),
                                                  jax.tree.leaves(prog)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(made))
    assert n == pytest.approx(2.733e9, rel=2e-3)         # the file's reckoning
    # every matrix a decoded token multiplies, once per application for
    # the shared blocks, the embedding once as the tied head
    L, A = cfg["num_hidden_layers"], len(vals["hybrid_layer_ids"])
    mats = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t)
                         if a.ndim == 3)
    per_token = mats(made["layers"]["mamba"]) - sum(
        int(np.prod(made["layers"]["mamba"][k].shape))
        for k in ("conv_w",)) + A * mats(made["shared"]) // 2 \
        + mats(made["apps"]) + int(np.prod(made["embed"].shape))
    assert work_hybrid.matmul_flops_per_token(cfg) == 2 * per_token
    f, b = work_hybrid.flash_decode_work(cfg, [10], kv_bytes=4)
    assert f == 4 * 32 * 224 * 10
    assert b == 2 * 32 * 10 * 224 * 4 + 2 * 32 * 224 * 4


def test_control_in_the_programs_place_reads_above_the_program(tmp_path):
    """The bf16 Zamba2 reference served in the program's place reads a
    wider mean squared gap than the program, which on the CPU computes in
    float32.  At this size the control stays under the full-size limit,
    which is set from chip readings of ``control_hybrid.py``, where the
    control's run comes out not correct (PERF.md)."""
    from chipbench import control_hybrid
    out = R.run_cell(tiny.Args("tiny-zamba.chat", seed=2 ** 33 + 7),
                     root=_root(tmp_path), require_tpu=False,
                     after=control_hybrid.control_in_place)
    assert out["result"]["correct"] is True, out["result"]["checks"]
    prog = out["result"]["checks"]["logit_gap_mean_sq"]["value"]
    ctl = out["extra"]["control_checks"]["logit_gap_mean_sq"]["value"]
    assert ctl > prog, out["extra"]
    assert out["extra"]["control_notes"]["served_mismatch_share"] > 0

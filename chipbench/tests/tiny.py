"""A tiny copy of the benchmark for CPU tests: the real harness, drivers,
readers and references, with small configurations and cells."""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

VGG = {
    "name": "tiny-vgg", "source": "test", "program_config": "vgg19",
    "driver": "cnn_camera", "input_hw": 32, "input_ch": 3, "kernel": 3,
    "pool": 2,
    "layers": [["conv", 8], ["pool"], ["conv", 16], ["pool"], ["flatten"],
               ["dense", 32], ["dense", 10]],
    "serving": {"batch": 1, "dtype": "float32", "precision": "highest",
                "split_for_mbps": {"20": 2, "5": 5}},
    "reduced": []}

QWEN = {
    "name": "tiny-qwen", "source": "test", "program_config": "qwen2.5-3b",
    "driver": "lm_sessions", "hidden_size": 1024, "intermediate_size": 1024,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 1e6, "tie_word_embeddings": True, "qkv_bias": True,
    "serving": {"dtype": "float32", "precision": "default", "num_slots": 2,
                "max_seq": 64, "decode_impl": "reference",
                "split_for_mbps": {"20": 3, "5": 1}},
    "reduced": []}

CAMERA = {
    "kind": "frames", "cycle_s": 2.0,
    "arrivals": {"process": "uniform", "rate": 10.0}, "queue_depth": 0,
    "images": 3, "latency_ms": 20.0,
    "link": [{"at": 0.0, "mbps": 20.0}, {"at": 1.0, "mbps": 5.0}],
    "strategy": "switch_b1"}

CHAT = {
    "kind": "sessions", "cycle_s": 2.0,
    "ticks": {"process": "uniform", "rate": 100.0}, "latency_ms": 20.0,
    "link": [{"at": 0.0, "mbps": 20.0}, {"at": 1.0, "mbps": 5.0}],
    "strategy": "switch_b2", "population": 8,
    "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "answer": {"median": 16, "sigma": 0.5, "min": 8, "max": 40}}


def make_root(tmp: Path, *, configs=None, traffic=None) -> Path:
    """A checkout-like directory: ``BENCHMARK.json`` and a copy of
    ``chipbench/`` holding the tiny configurations and traffic mixes."""
    configs = configs or {"tiny-vgg": VGG, "tiny-qwen": QWEN}
    traffic = traffic or {"tiny.camera": CAMERA, "tiny.chat": CHAT}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, cfg in configs.items():
        (tmp / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, t in traffic.items():
        (tmp / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    bench = copy.deepcopy(bench)
    bench["configs"] = [{"name": n, "source": "test",
                         "file": f"chipbench/configs/{n}.json",
                         "reduced": [], "why": "test"} for n in configs]
    cells = [("tiny-vgg.camera", "tiny-vgg", "tiny.camera"),
             ("tiny-qwen.chat", "tiny-qwen", "tiny.chat")]
    bench["workloads"] = [{"name": c, "config": k, "traffic": t,
                           "chips": 1, "why": "test"}
                          for c, k, t in cells if k in configs]
    real = {w["name"]: w for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]}
    rename = {"vgg19.camera.switch_b1": "tiny-vgg.camera",
              "qwen2.5-3b.chat.switch_b2": "tiny-qwen.chat"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]
                              if w in rename and w in real]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


class Args:
    def __init__(self, workload, seed=7, seconds=0.5, trace=0):
        self.workload, self.seed = workload, seed
        self.seconds, self.trace = seconds, trace

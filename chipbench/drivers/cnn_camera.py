"""A CNN served to a camera: ``CnnStageRunner`` -> ``PipelinePool`` ->
``PipelineManager`` -> ``ServingEngine`` on a ``VirtualClock``.

Each frame of the traffic really runs through the active compiled edge
and cloud stages; the engine composes the edge wall (times the program's
``edge_scale``), the priced link and the cloud wall on the stream clock,
and charges every repartition's measured wall to it.  Every frame the
window serves is kept (its logits stay on the device until the window
has closed) and compared with the plain reference afterwards.
"""
from __future__ import annotations

import gc

import jax
import numpy as np

from chipbench import traffic as T
from chipbench import weights
from chipbench.drivers import BenchEngine, program_config, schedule_link
from chipbench.reference import vgg

# Served logits vs the "highest"-precision float32 reference, as a share
# of the largest reference logit, worst frame of the window.  Set between
# chip readings of the program and of the three-pass control served in
# its place (``chipbench/control.py``); PERF.md gives the readings.
LOGITS_LIMIT = 2e-5


class Driver:
    kind = "frames"

    def __init__(self, cfg: dict, traffic: dict, seed: int, spans):
        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        self.serving = cfg["serving"]
        self.steps = T.link_steps(traffic, self.serving["split_for_mbps"])
        self.recording = False
        self.served = []            # (image index, logits, split) per frame
        self.attempted = self.failed = 0
        self.notes = {}

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        from repro.configs.base import CNNLayer
        from repro.core import NetworkModel, PipelineManager
        from repro.core.pool import PipelinePool
        from repro.core.stages import CnnStageRunner

        cfg = self.cfg
        jax.config.update("jax_default_matmul_precision",
                          self.serving["precision"])
        layers = []
        for kind, *arg in cfg["layers"]:
            layers.append(
                CNNLayer("conv", out_ch=arg[0], kernel=cfg["kernel"])
                if kind == "conv" else
                CNNLayer("pool", stride=cfg["pool"]) if kind == "pool" else
                CNNLayer("dense", units=arg[0]) if kind == "dense" else
                CNNLayer(kind))
        pcfg = program_config(cfg["program_config"], {
            "input_hw": cfg["input_hw"], "input_ch": cfg["input_ch"],
            "layers": tuple(layers), "num_classes": cfg["layers"][-1][1]})
        params = weights.cnn_params(cfg, self.seed)
        runner = CnnStageRunner(pcfg, params=params)
        self._check_layout(runner, params)
        imgs = weights.images(cfg, self.seed, int(self.traffic["images"]))
        self.images = [{"image": imgs[i:i + 1]}
                       for i in range(imgs.shape[0])]
        self._image_of = {id(d): i for i, d in enumerate(self.images)}
        driver = self

        class Pool(PipelinePool):
            def _new_pipeline(self, key):
                pipe = super()._new_pipeline(key)
                inner = pipe.process

                def process(inputs, **kw):
                    with driver.spans("frame"):
                        out = inner(inputs, **kw)
                    if driver.recording:
                        driver.served.append(
                            (driver._image_of[id(inputs)], out[0], pipe.split))
                    return out
                pipe.process = process
                return pipe

        lat = float(self.traffic["latency_ms"])
        last = self.steps[-1]
        net = NetworkModel(last[1], latency_ms=lat)
        pool = Pool(runner, net, self.images[0])
        # start on the split of the cycle's last step, so that every cycle
        # opens with the same repartition
        first_split = self.steps[0][2]
        self.mgr = PipelineManager(runner, last[2], net, self.images[0],
                                   pool=pool)
        self.mgr.pool.active.warm(self.images[0])
        strategy = self.traffic["strategy"]
        if strategy is not None and first_split != last[2]:
            # visit every split the cycle serves, so that each is compiled
            # (and in the persistent cache) before the window
            for split in (first_split, last[2]):
                self.mgr.set_network(NetworkModel(
                    self.steps[0][1] if split == first_split else last[1],
                    latency_ms=lat))
                self.mgr.repartition(strategy, split)
                self.mgr.pool.active.warm(self.images[0])
        self.active_split = last[2]
        self.runner = runner

    def _check_layout(self, runner, params) -> None:
        """The program's unit list matches the file's layer list."""
        kinds = [name.rstrip("0123456789") for name, _ in runner.units]
        want = [layer[0] for layer in self.cfg["layers"]]
        if kinds != want:
            raise ValueError(f"program units {kinds} differ from the "
                             f"file's layers {want}")

    # -- window ---------------------------------------------------------
    def start_window(self) -> None:
        self.recording = True

    def cycle(self, index: int) -> dict:
        from repro.serving import VirtualClock
        eng = BenchEngine(self.mgr, spans=self.spans, clock=VirtualClock(),
                          warmup=False,
                          queue_depth=int(self.traffic["queue_depth"]))
        self.active_split = schedule_link(
            eng, self.steps, self.traffic["strategy"], self.active_split,
            float(self.traffic["latency_ms"]))
        plan = T.frame_plan(self.traffic, self.seed, index)
        tl = eng.run([(t, self.images[i]) for t, i in plan],
                     duration=float(self.traffic["cycle_s"]))
        self.attempted += tl.arrived
        return {"timeline": tl, "reports": list(eng.reports)}

    def stop_window(self) -> None:
        self.recording = False
        self.mgr.drain()

    # -- after the window -------------------------------------------------
    def counts(self, cycles: list) -> dict:
        recs = [r for c in cycles for r in c["timeline"].records]
        return {"frames_arrived": len(recs),
                "frames_served": sum(r.served for r in recs),
                "frames_dropped_busy": sum(r.drop_reason == "busy"
                                           for r in recs),
                "frames_dropped_other": sum(r.dropped and
                                            r.drop_reason != "busy"
                                            for r in recs)}

    def free(self) -> None:
        """Release the program's state before the reference runs."""
        idx = np.asarray([i for i, _, _ in self.served], np.int64)
        splits = np.asarray([s for _, _, s in self.served], np.int64)
        # fetched one by one: a concatenate of thousands of operands would
        # compile a program of its own for every count
        served = (np.concatenate(jax.device_get([lg for _, lg, _ in
                                                 self.served]))
                  if self.served else np.zeros((0, 1)))
        self.served_arrays = (idx, served, splits)
        self.served = []
        self.mgr.close()
        del self.mgr, self.runner, self.images
        gc.collect()

    def check(self, cycles: list) -> list:
        """``[(name, value, limit)]``: every number compared, with its
        limit (a value must not exceed its limit)."""
        idx, served, splits = self.served_arrays
        params = weights.cnn_params(self.cfg, self.seed)
        imgs = weights.images(self.cfg, self.seed, int(self.traffic["images"]))
        ref = np.asarray(vgg.logits(self.cfg, params, imgs, "highest"),
                         np.float64)
        err = rel_err(served, ref[idx])
        splits_seen = sorted(set(splits.tolist()))
        want = sorted({s for _, _, s in self.steps})
        self.notes["frames_compared"] = int(len(idx))
        self.notes["splits_compared"] = splits_seen
        return [("logits_rel_err_max", err, LOGITS_LIMIT),
                ("splits_missing", float(len(set(want) - set(splits_seen))),
                 0.0)]


def rel_err(served, ref_rows) -> float:
    """Worst frame's largest logit error, as a share of that frame's
    largest reference logit."""
    if len(served) == 0:
        return float("inf")
    diff = np.abs(np.asarray(served, np.float64) - ref_rows).max(-1)
    scale = np.abs(ref_rows).max(-1)
    return float((diff / scale).max())

"""Slot-indexed multi-session decode serving.

``DecodeSession`` serves ONE stream; production edge-cloud decode means
many concurrent sessions with ragged context lengths sharing one
pipeline, all of whose state must survive a repartition together.  The
``SessionManager`` here generalises the session's per-unit KV/conv/SSM
entries into a **slot pool**:

* **Fixed bucket shapes** — every state buffer carries a leading
  ``(num_slots,)`` axis padded to the runner's ``max_seq``, so the
  compiled decode/recompute executables never re-specialise as sessions
  come and go.  Empty ("dead") slots ride along in the batch and are
  masked: every decode op is row-independent (causal attention, per-row
  rope/KV writes, masked-dt SSM updates), so a dead or newly-admitted
  slot can NEVER perturb a live slot's logits — the row-coupled MoE
  family is excluded for exactly this reason.
* **Mid-flight admission** — ``admit`` runs the runner's masked-prefill
  admission fn at a fixed ``(1, max_seq)`` bucket (one compile, ever)
  and places the resulting row state into a free slot, in one program
  over the whole pool, while the other slots keep decoding.
* **Step carry on the device** — each slot's boundary checkpoints and
  last logits stay on the device, slot-major like the state; one
  donated program per step writes the live rows and picks the next
  token, so a step brings back only its ``(num_slots, 1)`` token.
* **LRU / preemption eviction** — live per-slot state is priced with
  ``state_handoff.range_state_bytes`` against ``mem_budget_bytes``
  (the same accounting the pipeline pool uses for standby weights);
  over-budget admission parks the least-recently-used slot's state as a
  serialized payload that ``readmit`` restores bit-exactly later.
* **Batch hand-off** — the manager speaks ``DecodeSession``'s hand-off
  interface (``step_pos``/``subset``/``commit_step``/``export_layers``/
  ``import_layers``/``recompute_layers``), so ``StatefulPipelinePool``
  hands off the ENTIRE batch's state before the pointer swap with the
  crossover arm chosen once per batch: ``plan_handoff`` prices
  batch-linear bytes via ``batch=num_slots``, transfer serializes every
  slot's sliced KV in one payload, and the recompute arm replays the
  masked fixed-shape pass with a per-slot ``(num_slots,)`` length
  vector.  Per-slot epochs record which manager epoch last touched each
  slot, so a post-handoff slot can prove its state is current.

Locking: slot metadata (``_slots``/``_parked``) is guarded by a rank-47
lock — above the stateful runner's rank-42 lock, so the manager must
NEVER call into the runner's compile caches while holding its own lock
(admission and recompute resolve their compiled fns first, then take
the lock to commit).  See ``docs/serving.md`` for the full architecture.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import timing
from repro.core.concurrency import (RANK_SESSION_MANAGER, guarded_by,
                                    make_lock)
from repro.core.hardware import CLOUD_SPEC
from repro.core.network import NetworkModel
from repro.core.state_handoff import range_state_bytes
from repro.core.stateful import (HANDOFF_META_KEY, StatefulStageRunner,
                                 decode_payload, export_state, import_state,
                                 is_kv, payload_checksum, state_keys)
from repro.models import transformer as T


# -- moves of one slot's rows across the whole state pool ------------------
# Each is one compiled program over the pool's state dict and its step
# carry (the boundary checkpoints and last logits), with the slot index
# traced: one compile serves every slot, and the pool's own keys (KV only,
# or conv/SSM and KV) give each family its program.  Module-level, not in
# the runner's compile caches, whose lock ranks below the manager's.  The
# carry is donated: only the manager holds it (``snapshot`` copies it), so
# a move writes its rows in place instead of copying the whole buffer.

def _set_row(v, row, j):
    """``v`` with row ``j`` set to ``row``, whose one slot axis of size 1
    may sit before its data (an admission's checkpoints are
    ``(U, 1, max_seq, D)``): the reshape moves no element."""
    return jax.lax.dynamic_update_slice_in_dim(
        v, row.reshape((1,) + v.shape[1:]), j, 0)


@functools.partial(jax.jit, donate_argnums=1)
def _place_rows(cache, carry, rows, j):
    """``cache`` and ``carry`` with row ``j`` of each entry set to
    ``rows[k]``."""
    return ({k: _set_row(v, rows[k], j) for k, v in cache.items()},
            {k: _set_row(v, rows[k], j) for k, v in carry.items()})


@jax.jit
def _take_rows(cache, j):
    """Row ``j`` of every entry."""
    return {k: jax.lax.dynamic_index_in_dim(v, j, 0, keepdims=False)
            for k, v in cache.items()}


@functools.partial(jax.jit, donate_argnums=1)
def _clear_rows(cache, carry, j):
    """``cache`` and ``carry`` with row ``j`` of every entry zeroed."""
    def zero(v):
        return jax.lax.dynamic_update_slice_in_dim(
            v, jnp.zeros((1,) + v.shape[1:], v.dtype), j, 0)
    return ({k: zero(v) for k, v in cache.items()},
            {k: zero(v) for k, v in carry.items()})


def _greedy(logits):
    return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


_greedy_token = jax.jit(_greedy)


@functools.partial(jax.jit, donate_argnums=0)
def _commit_rows(carry, bounds, logits, at):
    """One decode step landed in the carry: each live row's ``(U, B, 1,
    D)`` checkpoints written at its position ``at[b]`` and its logits
    replaced; a dead row (``at[b]`` is ``max_seq``) keeps what it holds.
    Returns the carry and the next ``(B, 1)`` token, the greedy pick of
    the new logits.  One in-place slice update per row: a scatter over
    the two row axes would transpose the whole buffer on a TPU."""
    b = carry["bounds"]                              # (B, U, max_seq, D)
    S = b.shape[2]
    for r in range(b.shape[0]):
        p = jnp.minimum(at[r], S - 1)
        old = jax.lax.dynamic_slice(b, (r, 0, p, 0),
                                    (1, b.shape[1], 1, b.shape[3]))
        new = jnp.swapaxes(bounds[:, r:r + 1], 0, 1)
        b = jax.lax.dynamic_update_slice(
            b, jnp.where(at[r] < S, new, old), (r, 0, p, 0))
    lg = jnp.where((at < S)[:, None], logits, carry["logits"])
    return {"bounds": b, "logits": lg}, _greedy(lg)


class SlotPoolFull(RuntimeError):
    """No free slot and preemption is disabled (or nothing is evictable)."""


@dataclass
class Slot:
    """One session's seat in the pool.  ``epoch`` is the manager epoch
    that last mutated this slot — the per-slot version a post-handoff
    consistency check compares against."""
    index: int
    sid: Optional[str] = None
    pos: int = 0
    live: bool = False
    last_used: int = 0
    epoch: int = -1


@guarded_by("_lock", "_slots", "_parked", rank=RANK_SESSION_MANAGER)
class SessionManager:
    """Slot-indexed state pool speaking ``DecodeSession``'s interface.

    Drop-in for the ``session=`` seat of ``StatefulPipelinePool`` /
    ``StatefulEdgeCloudPipeline``: ``step_pos()`` returns a
    ``(num_slots,)`` position vector (dead slots at 0), so the compiled
    stages decode the whole ragged batch per step, and the hand-off
    primitives move/rebuild every slot's state at once.
    """

    def __init__(self, runner: StatefulStageRunner, *, num_slots: int,
                 mem_budget_bytes: Optional[int] = None,
                 allow_preempt: bool = True):
        if runner.cfg.family == "moe":
            raise ValueError(
                "slot pools require row-independent decode ops; the MoE "
                "family's capacity-factor routing couples batch rows, so "
                "a dead slot could perturb live logits")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.runner = runner
        self.cfg: ArchConfig = runner.cfg
        self.num_slots = int(num_slots)
        self.max_seq = runner.max_seq
        self.mem_budget_bytes = mem_budget_bytes
        self.allow_preempt = allow_preempt
        self.epoch = 0
        self.calib_spec = CLOUD_SPEC        # refined by the first admit()
        self._calibrated = False
        self._next_sid = 0
        self._clock = 0
        self._step_fn = None                # lazy local-decode jit
        self._lock = make_lock("session-manager", RANK_SESSION_MANAGER)
        self._slots: List[Slot] = [Slot(j) for j in range(self.num_slots)]
        self._parked: Dict[str, dict] = {}
        # fixed-bucket state buffers.  Shapes/dtypes come from one zero
        # pass of the admission fn — the same compile every later admit
        # reuses, so this costs nothing extra over the first admission.
        logits0, caches0, bounds0 = runner.admit_fn()(
            runner.params, jnp.zeros((1, self.max_seq), jnp.int32),
            jnp.int32(1))
        B = self.num_slots
        self.cache: Dict[str, Any] = {
            k: jnp.zeros((B,) + v.shape[1:], v.dtype)
            for k, v in caches0.items()}
        # the step carry, on the device and slot-major like the cache:
        # every slot's boundary checkpoints and its last logits
        self.carry: Dict[str, Any] = {
            "bounds": jnp.zeros((B, bounds0.shape[0]) + bounds0.shape[2:],
                                bounds0.dtype),   # (B, U, max_seq, D)
            "logits": jnp.zeros((B, logits0.shape[-1]), logits0.dtype)}
        self._next = None                   # greedy token of the carry
        self.tokens = np.zeros((B, self.max_seq), np.int32)

    # -- DecodeSession-compatible surface --------------------------------
    @property
    def batch(self) -> int:
        """The pipeline's batch axis IS the slot count."""
        return self.num_slots

    @property
    def pos(self) -> int:
        """Max live decode position: the bucket length hand-off pricing
        uses and KV exports slice to (every row is zero beyond its own
        prefix, so the shared slice loses nothing)."""
        with self._lock:
            return max((s.pos for s in self._slots if s.live), default=0)

    def step_pos(self):
        """Per-slot decode positions, ``(num_slots,)`` int32 — dead slots
        sit at 0 and decode into their own (masked) row only."""
        with self._lock:
            pos = np.asarray([s.pos for s in self._slots], np.int32)
        return timing.upload(pos)

    def next_token(self):
        """Greedy next token per slot, ``(num_slots, 1)`` on the device:
        the one the last commit picked, or, after a slot move, the pick
        of the carried logits (dead rows produce garbage tokens that only
        ever land in their own masked row)."""
        with self._lock:
            if self._next is None:
                self._next = _greedy_token(self.carry["logits"])
            return self._next

    def handoff_net(self, net: NetworkModel) -> NetworkModel:
        """Slot pools skip the single-stream serialization calibration
        (payloads are batch-sized; the wire model dominates)."""
        return net

    def subset(self, u0: int, u1: int) -> Dict[str, Any]:
        """The slot-pool state entries a stage over units [u0, u1) sees."""
        with self._lock:
            return {k: self.cache[k] for i in range(u0, u1)
                    for k in state_keys(self.cfg, i)}

    def commit_step(self, token, new_state: Dict[str, Any], bounds,
                    logits) -> None:
        """Land one whole-batch decode step: state buffers swap to the
        new batch, but tokens/bounds/logits commit per LIVE slot only —
        dead rows' garbage never reaches the bookkeeping buffers, so the
        zero-beyond-prefix invariant survives.  The bounds and logits stay
        on the device (one program writes them into the carry and picks
        the next token); only the step's ``(num_slots, 1)`` token comes
        back, for the host's token history."""
        tok = timing.fetch(token)
        with self._lock:
            live = [s for s in self._slots if s.live]
            for slot in live:
                if slot.pos >= self.max_seq:
                    raise RuntimeError(
                        f"slot {slot.sid!r} context full ({slot.pos} >= "
                        f"max_seq {self.max_seq})")
            self.cache.update(new_state)
            self.epoch += 1
            at = np.full(self.num_slots, self.max_seq, np.int32)
            for slot in live:
                at[slot.index] = slot.pos
            self.carry, self._next = _commit_rows(
                self.carry, bounds, logits, timing.upload(at))
            timing.count("carry_rows", len(live))
            for slot in live:
                self.tokens[slot.index, slot.pos] = tok[slot.index, 0]
                slot.pos += 1
                slot.epoch = self.epoch

    # -- admission --------------------------------------------------------
    def admit(self, prompt, sid: Optional[str] = None) -> str:
        """Prefill ``prompt`` into a free slot (mid-flight: the other
        slots' state is untouched — row independence is what the
        slot-isolation tests pin down).  With no free slot, preempts the
        LRU live slot (parking its state) when ``allow_preempt``;
        over-budget admission parks LRU slots until the pool fits.
        Returns the session id."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        L = int(prompt.shape[0])
        if not 0 < L <= self.max_seq:
            raise ValueError(f"prompt length {L} not in [1, {self.max_seq}]")
        r = self.runner
        with timing.span("sessions.admit", sid=sid):
            with timing.span("admit.prefill"):
                # resolve the compiled admission fn BEFORE taking our lock:
                # the runner's cache lock ranks below ours (42 < 47)
                admit_f = r.admit_fn()
                row = np.zeros((1, self.max_seq), np.int32)
                row[0, :L] = prompt
                tok = timing.upload(row)
                logits, caches, bounds = admit_f(r.params, tok, jnp.int32(L))
                timing.block(logits)
            if not self._calibrated:
                # warm second run prices THIS HOST's recompute throughput
                # for the hand-off planner, exactly like
                # DecodeSession.prefill
                with timing.span("admit.calibrate", timed=True) as m:
                    timing.block(admit_f(r.params, tok, jnp.int32(L))[0])
                self._calibrate(m.wall, L)
            with timing.span("admit.place"), self._lock:
                j = self._find_slot()
                slot = self._slots[j]
                self._place({**caches, "bounds": bounds, "logits": logits},
                            j)
                self.tokens[j] = row[0]
                if sid is None:
                    sid = f"s{self._next_sid}"
                    self._next_sid += 1
                self.epoch += 1
                slot.sid, slot.live, slot.pos, slot.epoch = sid, True, L, \
                    self.epoch
                self._touch(slot)
                self._evict_to_budget(keep=j)
        return sid

    def _calibrate(self, wall: float, toks: int) -> None:
        from repro.core.profiler import _layer_flops
        flops = sum(_layer_flops(self.cfg, k, tokens=toks, seq=toks)
                    for k in self.cfg.layer_kinds())
        if wall > 0 and flops > 0:
            self.calib_spec = dataclasses.replace(
                CLOUD_SPEC, name="host-calibrated", flops=flops / wall,
                mfu=1.0)
        self._calibrated = True

    def _touch(self, slot: Slot) -> None:    # holds: _lock
        self._clock += 1
        slot.last_used = self._clock

    def _find_slot(self) -> int:    # holds: _lock
        for slot in self._slots:
            if not slot.live:
                return slot.index
        if not self.allow_preempt:
            raise SlotPoolFull(f"all {self.num_slots} slots live and "
                               f"preemption is disabled")
        victim = min((s for s in self._slots if s.live),
                     key=lambda s: s.last_used)
        self._park(victim.index)
        return victim.index

    # -- memory accounting / eviction -------------------------------------
    def slot_state_bytes(self, pos: int) -> int:
        """Priced bytes of one slot's live state at context length
        ``pos`` — the same ``range_state_bytes`` pricing the hand-off
        planner uses (f32 state, one batch row, every layer)."""
        return range_state_bytes(self.cfg, 0, self.cfg.num_layers,
                                 seq_len=max(int(pos), 1), batch=1,
                                 act_bytes=4)

    def state_bytes(self) -> int:
        """Priced bytes of all live slots' state."""
        with self._lock:
            return sum(self.slot_state_bytes(s.pos)
                       for s in self._slots if s.live)

    def _evict_to_budget(self, keep: Optional[int] = None) -> None:  # holds: _lock
        if self.mem_budget_bytes is None:
            return
        while sum(self.slot_state_bytes(s.pos)
                  for s in self._slots if s.live) > self.mem_budget_bytes:
            victims = sorted((s for s in self._slots
                              if s.live and s.index != keep),
                             key=lambda s: s.last_used)
            if not victims:
                warnings.warn("session slot pool over memory budget but "
                              "nothing evictable", RuntimeWarning)
                break
            self._park(victims[0].index)

    def evict(self, sid: str) -> None:
        """Park ``sid``'s state (freeing its slot) for a later
        ``readmit``.  The parked payload uses the same serialized
        ``(dtype, shape, bytes)`` entries as ``export_layers``, so the
        round trip exercises the hand-off representation."""
        with timing.span("sessions.evict", sid=sid), self._lock:
            self._park(self._slot_index(sid))

    def _slot_index(self, sid: str) -> int:    # holds: _lock
        for slot in self._slots:
            if slot.live and slot.sid == sid:
                return slot.index
        raise KeyError(f"no live session {sid!r}")

    def _park(self, j: int) -> None:    # holds: _lock
        with timing.span("sessions.park", sid=self._slots[j].sid):
            self._park_slot(j)

    def _place(self, rows: Dict[str, Any], j: int) -> None:  # holds: _lock
        """Row ``j`` of every state and carry entry set to ``rows[k]``, in
        one program."""
        cache, self.carry = _place_rows(self.cache, self.carry, rows,
                                        np.int32(j))
        self.cache.update(cache)
        self._next = None
        timing.count("slot_rows")

    def _park_slot(self, j: int) -> None:    # holds: _lock
        slot = self._slots[j]
        # take and clear dispatch before the one fetch waits on the take
        taken = _take_rows({**self.cache, **self.carry}, np.int32(j))
        timing.count("slot_rows")
        cache, self.carry = _clear_rows(self.cache, self.carry, np.int32(j))
        self.cache.update(cache)
        self._next = None
        timing.count("slot_rows")
        rows = timing.fetch_all(taken)
        state: Dict[str, tuple] = {}
        for i in self.runner.units:
            for k in state_keys(self.cfg, i):
                arr = rows[k]
                if is_kv(k):                     # row KV: (KH, S, hd)
                    arr = arr[:, :slot.pos]
                state[k] = (str(arr.dtype), arr.shape, arr.tobytes())
        self._parked[slot.sid] = {
            "state": state,
            "tokens": self.tokens[j, :slot.pos].copy(),
            "bounds": rows["bounds"][:, :slot.pos],   # (U, pos, D)
            "logits": rows["logits"],
            "pos": slot.pos,
        }
        self.tokens[j] = 0
        self.epoch += 1
        slot.sid, slot.live, slot.pos, slot.epoch = None, False, 0, -1

    def readmit(self, sid: str) -> str:
        """Restore a parked session into a free slot, bit-exactly."""
        with self._lock:
            if sid not in self._parked:
                raise KeyError(f"no parked session {sid!r}")
            j = self._find_slot()
            parked = self._parked.pop(sid)
            slot = self._slots[j]
            pos = parked["pos"]
            rows = {}
            for k, (dtype, shape, buf) in parked["state"].items():
                arr = np.frombuffer(buf, dtype=dtype).reshape(shape)
                if is_kv(k):
                    full = np.zeros(self.cache[k].shape[1:], arr.dtype)
                    full[:, :arr.shape[1]] = arr
                    arr = full
                rows[k] = arr[None]
            b = parked["bounds"]
            rows["bounds"] = np.zeros(self.carry["bounds"].shape[1:],
                                      b.dtype)
            rows["bounds"][:, :pos] = b
            rows["logits"] = parked["logits"]
            self._place(timing.upload_all(rows), j)
            self.tokens[j, :pos] = parked["tokens"]
            self.epoch += 1
            slot.sid, slot.live, slot.pos, slot.epoch = sid, True, pos, \
                self.epoch
            self._touch(slot)
        return sid

    # -- introspection -----------------------------------------------------
    def session_ids(self) -> List[str]:
        with self._lock:
            return [s.sid for s in self._slots if s.live]

    def parked_ids(self) -> List[str]:
        with self._lock:
            return sorted(self._parked)

    def slot_info(self, sid: str) -> Slot:
        """A COPY of the session's slot record (pos, epoch, lru stamp)."""
        with self._lock:
            return dataclasses.replace(self._slots[self._slot_index(sid)])

    def logits_for(self, sid: str) -> np.ndarray:
        with self._lock:
            row = self.carry["logits"][self._slot_index(sid)]
        return timing.fetch(row)

    def tokens_for(self, sid: str) -> np.ndarray:
        with self._lock:
            j = self._slot_index(sid)
            return self.tokens[j, :self._slots[j].pos].copy()

    # -- batch hand-off primitives ----------------------------------------
    def export_layers(self, lo: int, hi: int) -> Tuple[Dict[str, tuple], int]:
        """Serialize layers [lo, hi) of the WHOLE slot pool: one payload,
        batch axis intact, KV sliced to the max live prefix (rows are
        zero beyond their own pos, so nothing is lost).  Same envelope
        (epoch, pos, crc) and wire format as ``DecodeSession``."""
        with self._lock:
            pos = max((s.pos for s in self._slots if s.live), default=0)
            payload, nbytes = export_state(
                self.cache, [k for i in range(lo, hi)
                             for k in state_keys(self.cfg, i)], pos)
            payload[HANDOFF_META_KEY] = (self.epoch, pos,
                                         payload_checksum(payload))
        return payload, nbytes

    def validate_payload(self, payload: Dict[str, tuple]) -> None:
        """Same integrity contract as ``DecodeSession.validate_payload``."""
        from repro.core.stateful import HandoffCorrupted
        meta = payload.get(HANDOFF_META_KEY)
        if meta is None:
            return
        epoch, _pos, crc = meta
        live_epoch = self.epoch
        if epoch != live_epoch:
            raise HandoffCorrupted(f"hand-off epoch {epoch} != manager "
                                   f"epoch {live_epoch}: stale payload")
        actual = payload_checksum(payload)
        if crc != actual:
            raise HandoffCorrupted(f"hand-off checksum mismatch: envelope "
                                   f"{crc:#010x} != bytes {actual:#010x}")

    def import_layers(self, payload: Dict[str, tuple]) -> None:
        """Deserialize a batch export back into the pool; validates and
        fully decodes BEFORE committing (corruption leaves the pool
        pristine for the recompute fallback)."""
        self.validate_payload(payload)
        decoded = decode_payload(payload)
        with self._lock:
            import_state(self.cache, decoded)

    def recompute_layers(self, lo: int, hi: int) -> None:
        """Rebuild layers [lo, hi) for EVERY slot from the per-slot
        boundary checkpoints: one masked fixed-shape pass with a
        ``(num_slots,)`` length vector — dead slots (length 0) rebuild to
        zero state, live slots to their exact pre-handoff state."""
        if lo >= hi:
            return
        r = self.runner
        fn = r.recompute_fn(lo, hi)          # runner lock first (42 < 47)
        with self._lock:
            x = self.carry["bounds"][:, lo]              # (B, max_seq, D)
            tokens = timing.upload(self.tokens)
            lengths = timing.upload(
                np.asarray([s.pos for s in self._slots], np.int32))
        caches = fn(r.params, x, tokens, lengths)
        timing.block(caches)
        with self._lock:
            self.cache.update(caches)

    # -- local decode (no edge/cloud split) -------------------------------
    def decode_step(self) -> np.ndarray:
        """One full-range decode step advancing every live slot — the
        ``BatchingServer`` path, no pipeline split.  Returns the
        ``(num_slots, 1)`` committed tokens."""
        r = self.runner
        U = len(r.units)
        if self.pos >= self.max_seq:
            raise RuntimeError(f"decode context full ({self.pos} >= "
                               f"max_seq {self.max_seq})")
        if self._step_fn is None:
            cfg = self.cfg
            decode = r._make_decode_fn(0, U)

            def step(params, tok, cache, pos):
                x = r.stream(params["embed"][tok])
                x, new, b = decode(params, x, cache, pos)
                h = T._apply_norm(cfg, params["final_norm"], r.hidden(x))
                logits = (h[:, -1] @ T.lm_head_weights(cfg, params)) \
                    .astype(jnp.float32)
                return logits, new, b

            self._step_fn = jax.jit(step)
        token = self.next_token()
        logits, new, b = self._step_fn(r.params, token, self.subset(0, U),
                                       self.step_pos())
        self.commit_step(token, new, b, logits)
        return timing.fetch(token)

    # -- test/benchmark support -------------------------------------------
    def snapshot(self) -> dict:
        """The pool's state; ``bounds`` ``(num_slots, U, max_seq, D)`` and
        ``logits`` are device copies, since later steps donate the
        carry."""
        with self._lock:
            return {"cache": dict(self.cache),
                    "tokens": self.tokens.copy(),
                    "bounds": self.carry["bounds"].copy(),
                    "logits": self.carry["logits"].copy(),
                    "slots": [dataclasses.replace(s) for s in self._slots],
                    "parked": dict(self._parked),
                    "epoch": self.epoch, "clock": self._clock}

    def restore(self, snap: dict) -> None:
        with self._lock:
            self.cache = dict(snap["cache"])
            self.tokens = snap["tokens"].copy()
            # copies: the carry is donated, the snapshot must outlive it
            self.carry = {k: jnp.array(snap[k]) for k in ("bounds", "logits")}
            self._next = None
            self._slots = [dataclasses.replace(s) for s in snap["slots"]]
            self._parked = dict(snap["parked"])
            self.epoch, self._clock = snap["epoch"], snap["clock"]


def make_session_manager(cfg: ArchConfig, params=None, *, split: int,
                         net: NetworkModel, num_slots: int,
                         max_seq: int = 128, seed: int = 0,
                         standby_split: Optional[int] = None,
                         warm_standbys: bool = False,
                         force_mode: Optional[str] = None,
                         mem_budget_bytes: Optional[int] = None,
                         session_budget_bytes: Optional[int] = None,
                         decode_impl: str = "auto", rolled: bool = True):
    """A ``PipelineManager`` whose pool serves a SLOT POOL of decode
    sessions.  Mirrors ``make_stateful_manager`` but seats a
    ``SessionManager`` (initially empty — ``admit`` sessions, then
    ``repartition``).  Returns ``(manager, session_manager)``."""
    from repro.core.stateful import StatefulPipelinePool, StatefulStageRunner
    from repro.core.switching import PipelineManager
    if params is None:
        params = T.init_model(cfg, jax.random.PRNGKey(seed))
    runner = StatefulStageRunner(cfg, params, max_seq=max_seq,
                                 decode_impl=decode_impl, rolled=rolled)
    sm = SessionManager(runner, num_slots=num_slots,
                        mem_budget_bytes=session_budget_bytes)
    pool = StatefulPipelinePool(runner, net, {"tokens": None},
                                session=sm, force_mode=force_mode,
                                warm_standbys=warm_standbys,
                                mem_budget_bytes=mem_budget_bytes)
    mgr = PipelineManager(runner, split, net, {"tokens": None},
                          pool=pool, standby_split=standby_split)
    return mgr, sm

"""Continuous-batching serving engine — multilevel scheduling for inference.

The paper's result (§5.3): aggregating many short tasks into one
scheduler-visible job recovers >90% utilization. For serving, a "task" is
one decode step of one request (milliseconds) and the "scheduler latency"
t_s is the per-dispatch overhead (Python driver + jit dispatch + launch).
Dispatching each request separately puts you in the paper's Case 2
(t ~< t_s); batching B requests into one ``serve_step`` dispatch is exactly
mimo-mode LLMapReduce bundling. benchmarks/dispatch_latency.py measures both
regimes and fits the same U(t) model.

Admission control reuses the core scheduler: each decode *lane* is a slot in
a ResourceManager; requests are single-task jobs placed FIFO. Lanes run
asynchronously (per-lane cache positions), i.e. continuous batching — a
finished request frees its lane immediately for the next admission.
"""
from __future__ import annotations

import collections
import itertools
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.job import Job, ResourceRequest, Task
from repro.core.resources import ResourceManager
from repro.models import build_model
from repro.models.transformer import BATCH_AXIS, cache_layout, init_caches
from repro.obs.spans import clock, mark, span

_req_ids = itertools.count(1)


def _write_lane(dst, src, lane):
    """Each 1-lane leaf of ``src`` into lane ``lane`` of its ``dst`` leaf."""
    return [jax.lax.dynamic_update_index_in_dim(d, x.astype(d.dtype), lane,
                                                BATCH_AXIS)
            for d, x in zip(dst, src)]


@dataclass
class ServeRequest:
    prompt: List[int]
    max_new_tokens: int = 16
    eos_token: int = -1
    request_id: int = field(default_factory=lambda: next(_req_ids))
    # filled by the engine, times in ns on the span clock (obs/spans.py)
    output: List[int] = field(default_factory=list)
    submit_time: int = 0
    first_token_time: int = 0
    done_time: int = 0

    @property
    def done(self) -> bool:
        return (len(self.output) >= self.max_new_tokens
                or (self.eos_token >= 0 and self.output
                    and self.output[-1] == self.eos_token))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, lanes: int = 8,
                 max_len: int = 512, greedy: bool = True, donate: bool = True):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.lanes = lanes
        self.max_len = max_len
        self.greedy = greedy
        # lane state
        self.caches = init_caches(cfg, lanes, max_len)
        self.cache_layout = cache_layout(cfg)
        self.positions = np.zeros((lanes,), np.int32)   # next write index
        self.lane_req: List[Optional[ServeRequest]] = [None] * lanes
        self.active_mask = np.zeros((lanes,), bool)
        self.pending: Deque[ServeRequest] = collections.deque()
        # admission control via the core scheduler's resource manager
        self.rm = ResourceManager()
        self.rm.add_nodes(lanes, slots=1)
        self._lane_jobs: Dict[int, Task] = {}   # lane -> admitted task
        self._decode = jax.jit(
            self._decode_fn, donate_argnums=(1,) if donate else ())
        self._prefill_one = jax.jit(self._prefill_fn)
        self._write_lane = jax.jit(
            _write_lane, donate_argnums=(0,) if donate else ())
        self.steps = 0
        self.decode_tokens = 0

    # ----------------------------------------------------------- jitted
    def _decode_fn(self, params, caches, tokens, positions):
        logits, caches = self.model.decode_step(params, tokens, caches,
                                                positions)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, caches

    def _prefill_fn(self, params, tokens):
        """Prefill one request padded to max_len-sized lane cache."""
        last, caches = self.model.prefill(params, tokens,
                                          max_len=self.max_len)
        next_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return next_tok, caches

    # ------------------------------------------------------------ admit
    def submit(self, req: ServeRequest) -> None:
        req.submit_time = clock()
        self.pending.append(req)

    def _admit(self) -> None:
        """Admit pending requests into free lanes. Spans, keyed by the
        request's id: ``engine.queue`` (submission to the start of its
        admission), ``engine.admit``, and inside it ``engine.prefill``,
        ``engine.scatter`` and ``engine.first_token``."""
        while self.pending:
            free = [i for i in range(self.lanes) if not self.active_mask[i]]
            if not free:
                return
            lane = free[0]
            req = self.pending.popleft()
            rid = req.request_id
            mark("engine.queue", req.submit_time, rid)
            with span("engine.admit", rid):
                self._admit_one(req, lane)

    def _admit_one(self, req: ServeRequest, lane: int) -> None:
        rid = req.request_id
        task_job = Job.array(1, name=f"req{rid}")
        self.rm.allocate(task_job.tasks[0], lane)
        self._lane_jobs[lane] = task_job.tasks[0]
        # prefill into this lane
        with span("engine.prefill", rid):
            prompt = jnp.asarray(req.prompt, jnp.int32)[None]
            next_tok, new_caches = self._prefill_one(self.params, prompt)
        with span("engine.scatter", rid):
            self._scatter_lane(lane, new_caches, rid)
        with span("engine.first_token", rid):
            tok = int(next_tok[0])
        req.output.append(tok)
        req.first_token_time = clock()
        if req.done:
            # generation stops at the step that produces EOS — when the
            # prefill token is already terminal (EOS, or max_new_tokens ==
            # 1), activating the lane would burn a decode dispatch and emit
            # one extra post-EOS token
            req.done_time = clock()
            self.rm.release(self._lane_jobs.pop(lane))
            return
        self.positions[lane] = len(req.prompt)
        self.lane_req[lane] = req
        self.active_mask[lane] = True

    def _scatter_lane(self, lane: int, src_caches, key=None) -> None:
        """Copy a 1-lane cache pytree into lane `lane` of the engine cache.

        Every leaf is written along ``BATCH_AXIS``, by the kind that
        ``cache_layout`` declares for it: the keys and values in one jitted
        call inside span ``engine.scatter.kv``, the recurrent state in
        another inside ``engine.scatter.state`` (keyed by ``key``), the
        engine's caches donated to each. A leaf with no declared kind, or
        whose shape does not fit the lane axis, raises: a leaf left as it
        was would hand one request's state to the next in the lane."""
        dst, tree = jax.tree_util.tree_flatten(self.caches)
        src = tree.flatten_up_to(src_caches)
        try:
            kinds = tree.flatten_up_to(self.cache_layout)
        except (TypeError, ValueError) as e:
            raise ValueError(f"a cache leaf has no declared kind: {e}") from e
        if not set(kinds) <= {"kv", "state"}:
            raise ValueError(f"a cache leaf has no declared kind: {kinds}")
        rest = lambda a: a.shape[:BATCH_AXIS] + a.shape[BATCH_AXIS + 1:]
        for d, x in zip(dst, src):
            if (d.ndim != x.ndim or d.shape[BATCH_AXIS] != self.lanes
                    or x.shape[BATCH_AXIS] != 1 or rest(d) != rest(x)):
                raise ValueError(f"a cache leaf of shape {x.shape} does not "
                                 f"fit lane axis {BATCH_AXIS} of {d.shape}")
        out = list(dst)
        for kind in ("kv", "state"):
            idx = [i for i, k in enumerate(kinds) if k == kind]
            if not idx:
                continue
            with span(f"engine.scatter.{kind}", key):
                new = self._write_lane([dst[i] for i in idx],
                                       [src[i] for i in idx], lane)
            for i, a in zip(idx, new):
                out[i] = a
        self.caches = tree.unflatten(out)

    # ------------------------------------------------------------- step
    def step(self) -> int:
        """Admit + one batched decode step; returns #active lanes.

        Spans of the decode step, keyed by its number (``steps`` before
        it): ``engine.prepare`` (the token batch), ``engine.decode`` (the
        jitted call), ``engine.sync`` (waiting for its tokens) and
        ``engine.retire`` (appending them, freeing finished lanes)."""
        self._admit()
        active = np.nonzero(self.active_mask)[0]
        if len(active) == 0:
            return 0
        n = self.steps
        with span("engine.prepare", n):
            tokens = np.zeros((self.lanes, 1), np.int32)
            for i in range(self.lanes):
                r = self.lane_req[i]
                if r is not None:
                    tokens[i, 0] = r.output[-1]
        with span("engine.decode", n):
            next_tok, self.caches = self._decode(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(self.positions))
        with span("engine.sync", n):
            next_np = np.asarray(next_tok)
        with span("engine.retire", n):
            self.steps += 1
            self.decode_tokens += len(active)
            for lane in active:
                req = self.lane_req[lane]
                req.output.append(int(next_np[lane]))
                self.positions[lane] += 1
                if req.done or self.positions[lane] >= self.max_len - 1:
                    req.done_time = clock()
                    self.active_mask[lane] = False
                    self.lane_req[lane] = None
                    task = self._lane_jobs.pop(lane, None)
                    if task is not None:
                        self.rm.release(task)
        return len(active)

    def run(self, requests: Sequence[ServeRequest]) -> Dict:
        """Serve a batch of requests to completion; returns summary stats."""
        t0 = clock()
        for r in requests:
            self.submit(r)
        while self.pending or self.active_mask.any():
            self.step()
        wall = (clock() - t0) * 1e-9
        lat = [(r.done_time - r.submit_time) * 1e-9 for r in requests]
        return {
            "wall_s": wall,
            "requests": len(requests),
            "decode_steps": self.steps,
            "decode_tokens": self.decode_tokens,
            "tokens_per_dispatch": self.decode_tokens / max(self.steps, 1),
            "mean_latency_s": float(np.mean(lat)) if lat else 0.0,
            "p99_latency_s": float(np.percentile(lat, 99)) if lat else 0.0,
            "throughput_tok_s": self.decode_tokens / max(wall, 1e-9),
        }

"""Task-set cells: the paper's constant-time task sets (arXiv 1705.03102,
Table 9) as device programs through ``Scheduler`` + ``JaxDispatchExecutor``.

Set-up builds the cluster (P one-slot nodes), the task weights from the
seed, and compiles the task program; a warm set of one task per slot goes
through the same scheduler and executor. The window then submits one job
array of P x tasks_per_processor tasks, runs it to completion, and submits
the next, until the window has closed. With ``"aggregation": "mimo"`` each
set is bundled by ``core.multilevel.aggregate`` into one bundle per slot
before it is submitted. A task counts once the scheduler reports it
complete within the window. Afterwards every task's result is checked to
be there once under its own key, and a sample of task indices drawn from
the seed is checked, in every set, against the plain reference.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import taskset


def run(ctx):
    from repro.core import (FAMILIES, Job, JobState, MultilevelConfig,
                            ResourceManager, Scheduler, aggregate)
    from repro.core.executor import JaxDispatchExecutor

    doc, mix, seed = ctx.config, ctx.traffic, ctx.seed
    P, n = doc["processors"], doc["task_matrix"]
    rounds, per = mix["rounds"], doc["tasks_per_processor"]
    mimo = mix["aggregation"] == "mimo"
    n_tasks = P * per

    # ----------------------------------------------------------- set-up
    rm = ResourceManager()
    rm.add_nodes(P, slots=doc["slots_per_node"])
    ex = JaxDispatchExecutor()
    sched = Scheduler(rm, profile=FAMILIES[doc["scheduler_profile"]],
                      executor=ex)
    w = taskset.task_weights(seed, n)
    ctx.mark("weights")
    # the control accumulates in bf16, one precision below the program's
    prog = jax.jit(taskset.task_program(
        n, rounds, jnp.bfloat16 if ctx.control else jnp.float32))

    def task_set(size):
        job = Job.array(size, payloads=[functools.partial(prog, w, i)
                                        for i in range(size)],
                        name="table9")
        return aggregate(job, P, MultilevelConfig(mode="mimo")) \
            if mimo else job

    jax.block_until_ready(prog(w, 0))
    ctx.mark("compile")
    warm = task_set(P)
    sched.submit(warm)
    sched.run()
    if warm.state is not JobState.COMPLETED or ex.errors:
        raise RuntimeError(f"warm set ended {warm.state}, {len(ex.errors)} "
                           f"errors")
    ex.results.clear()
    ctx.setup_done()
    ctx.log(tasks_per_set=n_tasks)

    # ----------------------------------------------------------- window
    rec = ctx.recorder()
    done_at = []                          # (wall time, tasks) per report
    per_bundle = -(-n_tasks // P) if mimo else 1
    sched.on_complete = lambda task, ok: done_at.append(
        (time.perf_counter(), ok))
    prof = None
    if ctx.trace:
        from repro.obs.profile import SelfProfiler
        prof = SelfProfiler().attach(sched)
        run_one = ex.run

        def traced_run(task, done):
            with rec.span("executor"):
                run_one(task, done)
        ex.run = traced_run
    jobs = []
    with ctx.window() as win:
        while time.perf_counter() < win.t0 + ctx.seconds:
            with rec.span("submit"):
                job = task_set(n_tasks)
                sched.submit(job)
            with rec.span("scheduler"):
                sched.run()
            jobs.append(job)
    ctx.read_memory()
    if prof is not None:
        prof.detach()

    # ---------------------------------------------------------- metrics
    end = win.t0 + ctx.seconds
    completed = sum(per_bundle for t, ok in done_at if ok and t <= end)
    ctx.end_to_end(tasks_per_s=completed / ctx.seconds)
    if prof is not None:
        phases = prof.report()
        self_s = sum(phases[p]["self_s"] for p in
                     ("admission", "cycle", "dispatch", "completion"))
        total = sum(per_bundle for _, ok in done_at if ok)
        ctx.observe(sched_self_s=self_s, tasks_done=total)

    # ------------------------------------------------------- correctness
    got, missing = {}, 0
    for job in jobs:
        res = []
        for b in range(job.n_tasks):
            r = ex.results.get((job.job_id, b))
            if r is None:
                missing += per_bundle
                res += [None] * per_bundle
            else:
                res += list(r) if mimo else [r]
        got[job.job_id] = res
    attempted = n_tasks * len(jobs)
    failed = missing + len(ex.errors) + sum(
        job.state is not JobState.COMPLETED for job in jobs)
    extra = len(ex.results) - sum(j.n_tasks for j in jobs)
    t_check = time.perf_counter()
    rng = np.random.default_rng([seed, 4])
    idx = np.sort(rng.choice(n_tasks, min(mix["check_sample"], n_tasks),
                             replace=False))
    want = taskset.checksums(w, idx, n, rounds)
    wrong = 0
    for res in got.values():
        for i, v in zip(idx, want):
            r = res[i]
            wrong += r is None or int(r) != int(v)
    ctx.attempted, ctx.failed = attempted, failed + wrong
    ctx.compare("missing_or_failed_tasks", failed + max(extra, 0), 0)
    ctx.compare("wrong_checksums", wrong, 0)
    ctx.log(sets=len(jobs), checked_per_set=len(idx),
            checked=len(idx) * len(jobs),
            check_s=time.perf_counter() - t_check)
    return ctx

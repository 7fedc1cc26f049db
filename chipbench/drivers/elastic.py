"""Drives the elastic trainer: ``VirtualCluster.train_step``, and the
traffic's events through ``inject_fail_stop`` and ``detect_and_recover``.

Set-up builds the cluster from the seed, compiles its step
(``compile_step``), and, where the traffic has events, warms the program
the cluster runs after them: a second cluster of the same job takes the
events and compiles its step, which leaves that program in the persistent
compilation cache.  The cluster then takes the traffic's first steps
through ``train_step`` itself; the window continues from there.
"""
from __future__ import annotations

import gc
import inspect
import time

import jax

from chipbench.reference.common import leaf_name, np_norm


def _cluster(cfg, job, seed):
    from repro.core.cluster import VirtualCluster
    from repro.optim.adam import AdamConfig
    kw = dict(global_batch=job["global_batch"], num_micro=job["num_micro"],
              seq_len=job["seq"], seed=seed,
              adam=AdamConfig(master_weights=True, **job["optimizer"]),
              snapshot_enabled=job["snapshot"])
    if "use_pallas" in inspect.signature(VirtualCluster).parameters:
        kw["use_pallas"] = True
    return VirtualCluster(cfg, job["dp"], job["pp"], **kw)


def _leaf_vectors(cl, comp: str):
    """Per-leaf float32 vectors of optimizer component ``comp`` (master,
    mu or nu), gathered from every stage and named as the model's leaves."""
    from repro.core.statespace import HEAD, STEM
    out = {}
    for st in cl.stages:
        full = st.full(comp)
        for pos, e in enumerate(st.entries):
            a, _ = st.table.layer_interval(pos)
            if e == STEM:
                tree, prefix = cl.stem, "stem"
            elif e == HEAD:
                tree, prefix = cl.head, "head"
            else:
                tree, prefix = cl.layer_params[e], f"layers/{e}"
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
                out[f"{prefix}/{leaf_name(path)}"] = full[a:a + leaf.size]
                a += leaf.size
    return out


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.job = ctx.traffic
        self.tokens_per_step = self.job["global_batch"] * self.job["seq"]
        self.losses = []
        self.cl = None
        self.master0 = None
        self.grads = None
        self.grad_norms = None
        self.change_norms = None
        self.programs = []

    # -- set-up -----------------------------------------------------------
    def setup(self):
        ctx, job = self.ctx, self.job
        span = ctx.spans.span
        with span("build"):
            self.cl = _cluster(ctx.cfg, job, ctx.seed)
        with span("compile_step"):
            self.programs += self.cl.compile_step()
        if job["events"]:
            with span("warm_after_events"):
                warm = _cluster(ctx.cfg, job, ctx.seed)
                for ev in job["events"]:
                    self._apply(warm, ev)
                warm.compile_step()
                del warm
                gc.collect()
        self.master0 = {k: v.copy() for k, v in
                        _leaf_vectors(self.cl, "master").items()}
        b1 = job["optimizer"]["b1"]
        for i in range(job["setup_steps"]):
            if i == job["setup_steps"] - 1:
                ctx.before_last_setup_step()
            with span("train_step"):
                self.losses.append(float(self.cl.train_step()))
            if i == 0:
                self.grads = {k: v / (1.0 - b1) for k, v in
                              _leaf_vectors(self.cl, "mu").items()}
                self.grad_norms = {k: np_norm(v)
                                   for k, v in self.grads.items()}

    def _apply(self, cl, ev):
        if ev["kind"] != "fail_stop":
            raise ValueError(f"unknown event kind {ev['kind']!r}")
        with self.ctx.spans.span("inject_fail_stop"):
            cl.inject_fail_stop(ev["dp"], ev["stage"])
        with self.ctx.spans.span("detect_and_recover"):
            cl.detect_and_recover()

    # -- window -----------------------------------------------------------
    def window(self, seconds: float) -> dict:
        span = self.ctx.spans.span
        events = sorted(self.job["events"], key=lambda e: e["after_window_steps"])
        steps, marks, pending = [], {}, False
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if events and events[0]["after_window_steps"] == len(steps):
                marks["event"] = time.perf_counter()
                self._apply(self.cl, events.pop(0))
                with span("recompile_step"):
                    self.programs += self.cl.compile_step()
                pending = True
            s0 = time.perf_counter()
            with span("train_step"):
                loss = float(self.cl.train_step())
            s1 = time.perf_counter()
            self.losses.append(loss)
            steps.append({"t0": s0, "t1": s1, "tokens": self.tokens_per_step,
                          "loss": loss})
            if pending:
                marks["first_step_after_event"] = s1
                pending = False
        m = _leaf_vectors(self.cl, "master")
        self.change_norms = {k: np_norm(m[k] - self.master0[k]) for k in m}
        return {"t0": t0, "t1": steps[-1]["t1"] if steps else t0,
                "steps": steps, "marks": marks}

    # -- what the reference is compared with ------------------------------
    def program_readings(self, n_window_steps: int) -> dict:
        n = self.job["setup_steps"] + n_window_steps
        return {"losses": self.losses[:n], "grad_norms": self.grad_norms,
                "change_norms": self.change_norms, "grads": self.grads}

    def release(self):
        self.cl = None
        self.master0 = None
        self.programs = []
        gc.collect()

"""Drives the plain jitted train step of ``launch/steps.py``
(``build_cell(cfg, "train", ...)``: forward, backward and the jnp AdamW)
on one chip, unsharded.

Set-up makes the weights with ``chipbench/weights.py`` (one jitted call
from the seed), the optimizer state with the program's ``init_opt_state``,
compiles the step, and takes the traffic's first steps through the same
compiled step and feed as the window.  The window keeps one step in
flight: it waits for the previous step's loss after queueing the next, so
the device never waits for the host and the host never runs ahead.
"""
from __future__ import annotations

import gc
import time

import jax

from chipbench import weights
import numpy as np

from chipbench.reference.common import diff_norms, named_leaves, norms, \
    step_tokens


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.job = ctx.traffic
        self.tokens_per_step = self.job["batch"] * self.job["seq"]
        self.losses = []
        self.grads = None
        self.grad_norms = None
        self.change_norms = None
        self.programs = []

    def _batch(self, step: int):
        toks = step_tokens(step, self.job["batch"], self.job["seq"],
                           self.ctx.cfg.vocab_size)
        return self.put({"tokens": toks, "labels": toks})

    def setup(self):
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_cell
        from repro.optim.adam import AdamConfig, init_opt_state
        ctx, job = self.ctx, self.job
        span = ctx.spans.span
        cfg = ctx.cfg
        adam = AdamConfig(master_weights=True, **job["optimizer"])
        mesh = make_mesh((1, 1), ("data", "model"))
        cell = build_cell(cfg, "train", job["seq"], job["batch"], mesh,
                          adam=adam, remat=job["remat"])
        ref_shapes = ctx.reference.param_shapes(ctx.spec)
        got = {k: (v.shape, v.dtype) for k, v in
               named_leaves(cell.arg_shapes[0]).items()}
        want = {k: (v.shape, v.dtype) for k, v in named_leaves(ref_shapes).items()}
        if got != want:
            raise ValueError(f"the program's parameter layout is not the "
                             f"reference's: {sorted(set(got.items()) ^ set(want.items()))[:4]}")
        self.init = lambda: weights.make(cell.arg_shapes[0], ctx.seed)
        with span("build"):
            params = self.init()
            opt = jax.jit(lambda p: init_opt_state(p, adam))(params)
            self.put = jax.jit(lambda b: b)
            b0 = self._batch(0)
        with span("compile_step"):
            self.step = jax.jit(cell.fn, donate_argnums=cell.donate).lower(
                params, opt, b0).compile()
        self.programs = [self.step]
        b1 = adam.b1
        for i in range(job["setup_steps"]):
            if i == job["setup_steps"] - 1:
                ctx.before_last_setup_step()
            with span("train_step"):
                params, opt, loss = self.step(params, opt, self._batch(i))
                self.losses.append(float(loss))
            if i == 0:
                mu = {k: v for k, v in named_leaves(opt["leaves"]).items()
                      if k.endswith("/mu")}
                self.grad_norms = {k[:-3]: v / (1.0 - b1)
                                   for k, v in norms(mu).items()}
                self.grads = {k[:-3]: np.asarray(v, np.float32).ravel()
                              / np.float32(1.0 - b1)
                              for k, v in jax.device_get(mu).items()}
                del mu
        master = jax.tree.map(lambda s: s["master"], opt["leaves"],
                              is_leaf=lambda x: isinstance(x, dict) and "mu" in x)
        self.change_norms = diff_norms(master, self.init())
        del master
        self.params, self.opt = params, opt

    def window(self, seconds: float) -> dict:
        span = self.ctx.spans.span
        params, opt = self.params, self.opt
        self.params = self.opt = None
        i = self.job["setup_steps"]
        steps, prev = [], None
        t0 = time.perf_counter()
        while True:
            with span("dispatch"):
                params, opt, loss = self.step(params, opt, self._batch(i))
            i += 1
            if prev is not None:
                with span("wait"):
                    prev_loss = float(prev)
                steps.append({"t1": time.perf_counter(), "loss": prev_loss,
                              "tokens": self.tokens_per_step})
            prev = loss
            if time.perf_counter() - t0 >= seconds:
                break
        with span("wait"):
            last = float(prev)
        steps.append({"t1": time.perf_counter(), "loss": last,
                      "tokens": self.tokens_per_step})
        self.params, self.opt = params, opt
        return {"t0": t0, "t1": steps[-1]["t1"], "steps": steps, "marks": {}}

    def program_readings(self, n_window_steps: int) -> dict:
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "change_norms": self.change_norms, "grads": self.grads}

    def release(self):
        self.params = self.opt = self.step = None
        self.programs = []
        gc.collect()

"""Drives the train cell of ``launch/steps.py`` (``build_cell(cfg, "train",
...)``: forward, backward and the jnp AdamW) compiled over the chips of one
host by ``compile_sharded``, on the traffic's (data, model) mesh, with the
pspecs of ``parallel/sharding.py``.

``plain_step.py``'s contract: one step in flight in the window, the
traffic's first steps taken in set-up through the same compiled step and
feed, and the same readings.  Here the weights are made under the step's
``out_shardings`` (never whole on one chip), the batch is put sharded over
``data``, and the first gradient is handed to the reference leaf by leaf,
each leaf put on the chips where the reference keeps that leaf
(``reference.shardings(spec)``) as the reference reads it: whole, it would
not fit one chip beside the reference's state.
"""
from __future__ import annotations

from collections.abc import Mapping

import jax
import numpy as np

from chipbench import weights
from chipbench.drivers import plain_step
from chipbench.reference.common import diff_norms, named_leaves, norms


class OnChips(Mapping):
    """Host arrays by leaf name, each put on the chips as it is read."""

    def __init__(self, host: dict, shapes: dict, shardings: dict):
        self.host, self.shapes, self.shardings = host, shapes, shardings

    def __getitem__(self, name):
        return jax.device_put(self.host[name].reshape(self.shapes[name]),
                              self.shardings[name])

    def __iter__(self):
        return iter(self.host)

    def __len__(self):
        return len(self.host)


class Run(plain_step.Run):
    def setup(self):
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_cell, compile_sharded
        from repro.optim.adam import AdamConfig, init_opt_state
        ctx, job = self.ctx, self.job
        span = ctx.spans.span
        adam = AdamConfig(master_weights=True, **job["optimizer"])
        mesh = make_mesh(tuple(job["mesh"].values()), tuple(job["mesh"]))
        cell = build_cell(ctx.cfg, "train", job["seq"], job["batch"], mesh,
                          adam=adam, remat=job["remat"])
        shapes = cell.arg_shapes[0]
        got = {k: (v.shape, v.dtype) for k, v in named_leaves(shapes).items()}
        want = {k: (v.shape, v.dtype) for k, v in
                named_leaves(ctx.reference.param_shapes(ctx.spec)).items()}
        if got != want:
            raise ValueError(f"the program's parameter layout is not the "
                             f"reference's: {sorted(set(got.items()) ^ set(want.items()))[:4]}")
        with span("compile_step"):
            self.step = compile_sharded(cell, mesh)
        self.programs = [self.step.compiled]
        at_params, at_opt, at_batch = self.step.in_shardings
        self.shapes = {k: v.shape for k, v in named_leaves(shapes).items()}
        self.init = jax.jit(lambda: weights.make(shapes, ctx.seed),
                            out_shardings=at_params)
        with span("build"):
            params = self.init()
            opt = jax.jit(lambda p: init_opt_state(p, adam),
                          out_shardings=at_opt)(params)
            self.put = jax.jit(lambda b: b, out_shardings=at_batch)
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

    def program_readings(self, n_window_steps: int) -> dict:
        return dict(super().program_readings(n_window_steps),
                    grads=OnChips(self.grads, self.shapes, named_leaves(
                        self.ctx.reference.shardings(self.ctx.spec))))

"""Step functions + input specs for every (arch x shape) dry-run cell.

Cell kinds:
  train_*   -> train_step(params, opt_state, batch) -> (params, opt_state, loss)
  prefill_* -> prefill_step(params, caches, tokens[, extras]) -> (logits, caches)
  decode_* / long_* -> decode_step(params, caches, tokens, index[, extras])

Everything lowers from ShapeDtypeStructs — no allocation at full scale.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import registry as R
from repro.models import transformer as T
from repro.models import encdec as E
from repro.launch.hlo_analysis import executed_collective_bytes
from repro.models.config import ModelConfig
from repro.optim.adam import AdamConfig, adam_update, init_opt_state, opt_state_shapes
from repro.parallel import sharding as S
from repro.spans import span


@dataclasses.dataclass
class Cell:
    kind: str                       # "train" | "prefill" | "decode"
    fn: Callable
    arg_shapes: Tuple[Any, ...]     # ShapeDtypeStruct pytrees
    arg_pspecs: Tuple[Any, ...]     # PartitionSpec pytrees
    out_pspecs: Any
    donate: Tuple[int, ...]


def shape_kind(shape_name: str) -> str:
    if shape_name.startswith("train"):
        return "train"
    if shape_name.startswith("prefill"):
        return "prefill"
    return "decode"


def _batch_shapes(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, Any]:
    sh: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.is_encdec:
        sh["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.max_source_positions, cfg.d_model), cfg.jnp_dtype)
    if cfg.frontend_embeds:
        sh["prefix_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.frontend_embeds, cfg.d_model), cfg.jnp_dtype)
    return sh


def build_cell(cfg: ModelConfig, shape_name: str, seq: int, batch: int,
               mesh, adam: Optional[AdamConfig] = None,
               remat: bool = True) -> Cell:
    kind = shape_kind(shape_name)
    adam = adam or AdamConfig()
    params_sh = R.model_param_shapes(cfg)
    pspec_params = S.param_pspecs(cfg, mesh, params_sh)

    if kind == "train":
        batch_sh = _batch_shapes(cfg, batch, seq)
        opt_sh = opt_state_shapes(params_sh, adam)
        pspec_opt = jax.tree.map(
            lambda _: None, opt_sh)  # replaced below: mirror params rules
        pspec_opt = _opt_pspecs_like(params_sh, pspec_params, opt_sh)
        pspec_batch = S.batch_pspecs(cfg, mesh, batch_sh)
        loss_fn = R.make_train_loss(cfg, remat=remat)

        def train_step(params, opt_state, batch_):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch_)
            new_params, new_opt = adam_update(params, grads, opt_state, adam)
            return new_params, new_opt, loss

        from jax.sharding import PartitionSpec as P
        return Cell("train", train_step,
                    (params_sh, opt_sh, batch_sh),
                    (pspec_params, pspec_opt, pspec_batch),
                    (pspec_params, pspec_opt, P()),
                    donate=(0, 1))

    # serving cells
    if cfg.is_encdec:
        return _encdec_serving_cell(cfg, kind, seq, batch, mesh,
                                    params_sh, pspec_params)
    max_len = seq
    caches_sh = T.cache_shapes(cfg, batch, max_len)
    pspec_caches = S.cache_pspecs(cfg, mesh, caches_sh)
    from jax.sharding import PartitionSpec as P
    if kind == "prefill":
        tok_sh = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

        def prefill_step(params, caches, tokens):
            return T.prefill(params, cfg, tokens, caches)

        return Cell("prefill", prefill_step,
                    (params_sh, caches_sh, tok_sh),
                    (pspec_params, pspec_caches, S._spec(mesh, (batch, seq),
                                                         S.dp_axes(mesh), None)),
                    (P(), pspec_caches),
                    donate=(1,))

    tok_sh = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    idx_sh = jax.ShapeDtypeStruct((), jnp.int32)

    def decode_step(params, caches, tokens, index):
        return T.decode_step(params, cfg, tokens, caches, index)

    return Cell("decode", decode_step,
                (params_sh, caches_sh, tok_sh, idx_sh),
                (pspec_params, pspec_caches,
                 S._spec(mesh, (batch, 1), S.dp_axes(mesh), None), P()),
                (P(), pspec_caches),
                donate=(1,))


def _encdec_serving_cell(cfg, kind, seq, batch, mesh, params_sh, pspec_params):
    from jax.sharding import PartitionSpec as P
    caches_sh = jax.eval_shape(lambda: E.init_decoder_caches(cfg, batch, seq))
    pspec_caches = S.cache_pspecs(cfg, mesh, caches_sh)
    enc_sh = jax.ShapeDtypeStruct((batch, cfg.max_source_positions, cfg.d_model),
                                  cfg.jnp_dtype)
    enc_spec = S._spec(mesh, enc_sh.shape, S.dp_axes(mesh), None, None)
    if kind == "prefill":
        tok_sh = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

        def prefill_step(params, caches, tokens, frames):
            enc = E.encode(params, cfg, frames)
            logits, caches = E.decode(params, cfg, tokens, enc,
                                      caches=caches, cache_index=0)
            return logits[:, -1:, :], caches

        frames_sh = jax.ShapeDtypeStruct(
            (batch, cfg.max_source_positions, cfg.d_model), cfg.jnp_dtype)
        return Cell("prefill", prefill_step,
                    (params_sh, caches_sh, tok_sh, frames_sh),
                    (pspec_params, pspec_caches,
                     S._spec(mesh, (batch, seq), S.dp_axes(mesh), None), enc_spec),
                    (P(), pspec_caches), donate=(1,))

    tok_sh = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    idx_sh = jax.ShapeDtypeStruct((), jnp.int32)

    def decode_step(params, caches, tokens, enc_out, index):
        return E.encdec_decode_step(params, cfg, tokens, enc_out, caches, index)

    return Cell("decode", decode_step,
                (params_sh, caches_sh, tok_sh, enc_sh, idx_sh),
                (pspec_params, pspec_caches,
                 S._spec(mesh, (batch, 1), S.dp_axes(mesh), None),
                 enc_spec, P()),
                (P(), pspec_caches), donate=(1,))


class ShardedStep:
    """A train cell compiled over a mesh (:func:`compile_sharded`).

    Called as the cell's ``fn`` (params, opt_state, batch), each call under
    ``span("sharded.step", step=i, collective_bytes=...)``; the call only
    dispatches, so the span ends before the device does.  ``in_shardings``
    place the arguments: weights made under ``jax.jit(...,
    out_shardings=in_shardings[0])`` are never whole on one chip."""

    def __init__(self, compiled, in_shardings, collective_bytes: int):
        self.compiled = compiled
        self.in_shardings = in_shardings
        self.collective_bytes = collective_bytes
        self.calls = 0

    def __call__(self, *args):
        with span("sharded.step", step=self.calls,
                  collective_bytes=self.collective_bytes):
            self.calls += 1
            return self.compiled(*args)


def compile_sharded(cell: Cell, mesh) -> ShardedStep:
    """Place and compile a train ``cell`` over ``mesh``: its pspecs become
    the shardings of its arguments and results, its state is donated, and
    it is lowered from its shapes and compiled under
    ``span("compile.sharded", collective_bytes=...)``.  ``collective_bytes``
    is ``hlo_analysis.executed_collective_bytes`` of the compiled (per-chip)
    program, summed over kinds: the operand bytes of the collectives one
    chip starts in a step, a layer loop's once a layer."""
    ins = tuple(S.to_shardings(mesh, p) for p in cell.arg_pspecs)
    fn = jax.jit(cell.fn, in_shardings=ins,
                 out_shardings=S.to_shardings(mesh, cell.out_pspecs),
                 donate_argnums=cell.donate)
    with span("compile.sharded") as sp:
        compiled = fn.lower(*cell.arg_shapes).compile()
        nbytes = int(executed_collective_bytes(compiled.as_text())["total"])
        sp.set_metadata(collective_bytes=nbytes)
    return ShardedStep(compiled, ins, nbytes)


def _opt_pspecs_like(params_sh, pspec_params, opt_sh):
    """Adam leaves {mu, nu, master} share their param's PartitionSpec; the
    scalar step is replicated."""
    from jax.sharding import PartitionSpec as P

    leaves_spec = jax.tree.map(
        lambda spec: {"mu": spec, "nu": spec, "master": spec},
        pspec_params, is_leaf=lambda x: isinstance(x, P))
    return {"leaves": leaves_spec, "step": P()}

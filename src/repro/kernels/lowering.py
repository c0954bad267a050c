"""Backend-aware lowering for the Pallas kernel surface.

Every kernel in this package is one *blocked program*: a grid, a set of
``BlockSpec``-style (block_shape, index_map) pairs, and a block function
that maps input block **values** to output block values.  The math
lives entirely in the block function — refs are touched only at
whole-block load/store boundaries — so a single body serves three
execution modes:

  ``pallas``    — real ``pl.pallas_call`` lowering.  Only available on
                  backends with a Pallas compiler (TPU Mosaic, GPU
                  Triton); CPU raises in upstream JAX.
  ``interpret`` — ``pl.pallas_call(interpret=True)``: the Pallas
                  interpreter walks the grid in Python.  Slow, but runs
                  everywhere and is the debugging/conformance anchor.
  ``xla``       — the Triton/Mosaic-free compiled path: the *same*
                  (grid, BlockSpec, block_fn) program executed as pure
                  XLA — a ``lax.fori_loop`` over the flattened grid with
                  ``dynamic_slice``/``dynamic_update_slice`` block
                  movement — which jit-compiles to native code on any
                  backend, including CPU where Pallas cannot lower.

``mode="compiled"`` resolves to ``pallas`` where a real lowering exists
and ``xla`` otherwise, so callers can ask for "fast and compiled"
without caring which compiler provides it.  The environment variable
``REPRO_KERNEL_MODE`` overrides the *default* resolution (it never
overrides an explicit ``mode=`` argument), which gives CI an
interpret-only leg on the CPU.  On a TPU the override may only ask for
``pallas``: a default call there never runs in a slower mode unseen.

Operands may be *scalar-prefetched* (Pallas TPU ``PrefetchScalarGridSpec``):
the first ``num_scalar_prefetch`` operands are small int32 tables that
live in SMEM on the TPU, every index map receives them after the grid
coordinates, and the block function receives the grid coordinates and
the tables ahead of its blocks.  This is how a block's position can
depend on data (a gather's row ids) and how a per-row scalar (a decode
row's valid length) reaches the body without a sub-(8, 128) block.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MODES = ("pallas", "interpret", "xla", "compiled")

_ENV_MODE = "REPRO_KERNEL_MODE"


@dataclasses.dataclass(frozen=True)
class Spec:
    """One operand's blocking: shape of the block moved per grid step
    plus the grid-coords -> *block index* map (Pallas BlockSpec
    semantics: element offset = block_index * block_shape)."""
    block_shape: tuple[int, ...]
    index_map: Callable[..., tuple[Any, ...]]

    def to_pallas(self) -> pl.BlockSpec:
        return pl.BlockSpec(self.block_shape, self.index_map)


def supports_pallas_lowering(backend: str | None = None) -> bool:
    """True when ``pl.pallas_call(interpret=False)`` has a real compiler
    on the active (or given) JAX backend."""
    b = backend or jax.default_backend()
    return b in ("tpu", "gpu", "cuda", "rocm")


def resolve_mode(interpret: bool | None = None, mode: str | None = None,
                 backend: str | None = None) -> str:
    """Resolve user intent to a concrete mode: 'pallas'|'interpret'|'xla'.

    Explicit ``mode`` wins; otherwise the legacy ``interpret`` flag maps
    True -> interpret, False/None -> compiled.  ``REPRO_KERNEL_MODE``
    overrides only this default resolution, never an explicit ``mode``.
    """
    if mode is None:
        env = os.environ.get(_ENV_MODE)
        on_tpu = (backend or jax.default_backend()) == "tpu"
        if env and env != "pallas" and on_tpu:
            raise ValueError(
                f"{_ENV_MODE}={env!r} would run every default kernel call "
                "on the TPU in a non-Mosaic mode; unset it or use 'pallas'")
        mode = env or ("interpret" if interpret is True else "compiled")
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mode == "compiled":
        mode = "pallas" if supports_pallas_lowering(backend) else "xla"
    return mode


def _unravel(step: jax.Array, grid: Sequence[int]) -> tuple[jax.Array, ...]:
    """Flat grid step -> coords, last dimension fastest (Pallas order)."""
    coords = []
    for size in reversed(grid):
        coords.append(step % size)
        step = step // size
    return tuple(reversed(coords))


def _block_starts(spec: Spec, coords: Sequence[jax.Array],
                  scalars: Sequence[jax.Array] = ()) -> tuple[jax.Array, ...]:
    idx = spec.index_map(*coords, *scalars)
    if len(idx) != len(spec.block_shape):
        raise ValueError(
            f"index_map produced {len(idx)} coords for block rank "
            f"{len(spec.block_shape)}")
    return tuple(jnp.asarray(i, jnp.int32) * b
                 for i, b in zip(idx, spec.block_shape))


def _xla_call(block_fn: Callable, grid: Sequence[int], in_specs: Sequence[Spec],
              out_specs: Sequence[Spec],
              out_shapes: Sequence[jax.ShapeDtypeStruct], args: Sequence,
              n_scalar: int):
    """Execute the blocked program as pure XLA ops (the interpreter-bypass
    path).  Each grid step slices its input blocks, runs the block
    function, and writes the output blocks back; XLA compiles the loop
    to native code on every backend."""
    steps = math.prod(grid)
    scalars, args = args[:n_scalar], args[n_scalar:]
    outs0 = [jnp.zeros(s.shape, s.dtype) for s in out_shapes]

    def one_step(step, outs):
        coords = _unravel(jnp.asarray(step, jnp.int32), grid)
        ins = [lax.dynamic_slice(a, _block_starts(s, coords, scalars),
                                 s.block_shape)
               for a, s in zip(args, in_specs)]
        res = block_fn(coords, *scalars, *ins) if n_scalar else block_fn(*ins)
        res = res if isinstance(res, (tuple, list)) else (res,)
        return [lax.dynamic_update_slice(o, v.astype(o.dtype),
                                         _block_starts(s, coords, scalars))
                for o, v, s in zip(outs, res, out_specs)]

    if steps == 1:
        outs = one_step(0, outs0)
    else:
        outs = lax.fori_loop(0, steps, one_step, outs0)
    return tuple(outs)


def _pallas_wrap(block_fn: Callable, n_in: int, n_scalar: int,
                 n_grid: int) -> Callable:
    """Adapt a value->value block function to a Pallas ref kernel:
    whole-block loads, call, whole-block stores.  Scalar-prefetch refs
    stay refs (SMEM on the TPU) and are indexed by the body."""
    def kernel(*refs):
        scalars, refs = refs[:n_scalar], refs[n_scalar:]
        ins = [r[...] for r in refs[:n_in]]
        if n_scalar:
            coords = tuple(pl.program_id(a) for a in range(n_grid))
            res = block_fn(coords, *scalars, *ins)
        else:
            res = block_fn(*ins)
        res = res if isinstance(res, (tuple, list)) else (res,)
        for r, v in zip(refs[n_in:], res):
            r[...] = v.astype(r.dtype)
    return kernel


def _grid_spec(grid, in_specs, out_specs, n_scalar: int):
    kw = dict(grid=grid, in_specs=[s.to_pallas() for s in in_specs],
              out_specs=[s.to_pallas() for s in out_specs])
    if n_scalar:
        return pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=n_scalar, **kw)
    return pl.GridSpec(**kw)


def grid_call(block_fn: Callable, *, grid: Sequence[int],
              in_specs: Sequence[Spec], out_specs: Sequence[Spec],
              out_shapes: Sequence[jax.ShapeDtypeStruct], mode: str,
              num_scalar_prefetch: int = 0,
              unpack: bool | None = None) -> Callable:
    """Build the executable for one blocked kernel program.

    Returns ``f(*args) -> out`` (single out_shape) or ``-> tuple``.
    ``mode`` must already be resolved ('pallas'|'interpret'|'xla').
    With ``num_scalar_prefetch = k`` the first k operands are int32
    tables: index maps are called as ``index_map(*coords, *tables)`` and
    the block function as ``block_fn(coords, *tables, *blocks)``.
    """
    grid = tuple(int(g) for g in grid)
    out_shapes = list(out_shapes)
    single = len(out_shapes) == 1 if unpack is None else unpack
    n_scalar = int(num_scalar_prefetch)

    def call(*args):
        if len(args) != n_scalar + len(in_specs):
            raise ValueError(f"expected {n_scalar + len(in_specs)} operands, "
                             f"got {len(args)}")
        if mode == "xla":
            outs = _xla_call(block_fn, grid, in_specs, out_specs,
                             out_shapes, args, n_scalar)
        elif mode in ("pallas", "interpret"):
            outs = pl.pallas_call(
                _pallas_wrap(block_fn, len(in_specs), n_scalar, len(grid)),
                grid_spec=_grid_spec(grid, in_specs, out_specs, n_scalar),
                out_shape=out_shapes,
                interpret=(mode == "interpret"),
            )(*args)
            outs = tuple(outs) if isinstance(outs, (tuple, list)) else (outs,)
        else:
            raise ValueError(f"unresolved mode {mode!r}; call resolve_mode")
        return outs[0] if single else tuple(outs)

    return call

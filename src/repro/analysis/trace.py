"""Jaxpr tracing utilities: pallas_call extraction, DMA events, liveness.

Everything here works on the *abstract* jaxpr jax produces on CPU — no
TPU, no execution. The wrappers normalize the handful of jax internals the
rules need (kernel operand roles, memory spaces, DMA event structure,
BlockSpec index maps) behind small dataclasses so a jax version bump
breaks one file, not every rule.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.extend import core as jax_core


# --------------------------------------------------------------------------
# generic jaxpr walking
# --------------------------------------------------------------------------

def _param_jaxprs(eqn) -> Iterator:
    """Yield every sub-jaxpr hiding in an eqn's params (cond branches,
    while/scan bodies, pjit bodies, shard_map bodies, ...)."""
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            # ClosedJaxpr first: it proxies .eqns, so the order matters
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):  # raw Jaxpr
                yield x


def iter_eqns(jaxpr, *, into: Tuple[str, ...] = ()) -> Iterator:
    """Depth-first over every eqn of ``jaxpr`` and all nested sub-jaxprs.

    ``into`` restricts recursion to eqns whose primitive is named there;
    empty means recurse through everything.
    """
    for eqn in jaxpr.eqns:
        yield eqn
        if into and eqn.primitive.name not in into:
            continue
        for sub in _param_jaxprs(eqn):
            yield from iter_eqns(sub, into=into)


def primitive_counts(jaxpr) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
    return out


# --------------------------------------------------------------------------
# kernel (pallas_call) artifacts
# --------------------------------------------------------------------------

def _memory_space(aval) -> str:
    """Normalize a kernel-ref aval's memory space to one of
    ``vmem | smem | any | semaphore | other``. Pallas prints block-mapped
    refs as ``MemRef<None>`` — the default space, which is VMEM."""
    space = getattr(aval, "memory_space", None)
    name = str(space).lower() if space is not None else "none"
    for key in ("semaphore", "smem", "vmem", "any"):
        if key in name:
            return key
    if name in ("none", "memoryspace.none"):
        return "vmem"
    return "other"


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dtype).itemsize


@dataclasses.dataclass
class KernelOperand:
    """One kernel invar: its role in the grid spec plus its block mapping
    (``None`` for scalar-prefetch operands, ANY-memory refs without a
    block, and scratch)."""

    index: int            # position among kernel invars
    role: str             # 'index' | 'input' | 'output' | 'scratch'
    space: str            # _memory_space() of the ref aval
    aval: object
    block_mapping: Optional[object] = None  # pallas BlockMapping

    @property
    def block_shape(self) -> Optional[Tuple[int, ...]]:
        if self.block_mapping is None:
            return None
        return tuple(
            int(getattr(b, "block_size", b))
            for b in self.block_mapping.block_shape if not _is_squeezed(b)
        ) or (1,)

    @property
    def dtype(self):
        return getattr(self.aval, "dtype", None)


def _is_squeezed(dim) -> bool:
    # pallas marks BlockSpec dims mapped with None as pl.Squeezed
    return dim is None or isinstance(dim, pl.Squeezed)


def _is_ref(v) -> bool:
    return isinstance(getattr(v, "aval", None), jax.ref.AbstractRef)


@dataclasses.dataclass
class DmaEvent:
    """One ``dma_start`` / ``dma_wait`` eqn, normalized.

    ``key`` identifies the logical copy: the (semaphore var, src ref var,
    dst ref var) triple — a wait matches the start with the same key.
    ``region`` is the straight-line context: () for the kernel body,
    ('cond', i, b) appended per enclosing branch b of the cond at body
    position i. ``position`` orders events by their outermost body index.
    """

    kind: str                      # 'start' | 'wait'
    key: Tuple
    position: int
    region: Tuple
    src_space: str
    dst_space: str
    src_var: object
    dst_var: object
    index_vars: Tuple              # dynamic index operands of the transfer


def _dma_refs(eqn):
    """Split a dma eqn's invars into (src ref, dst ref, sem ref, index
    vars). Layout: [src, *src_idx, dst, *dst_idx, sem, ...] —
    refs are the invars with ref avals, in order src, dst, sem."""
    refs = [v for v in eqn.invars if _is_ref(v)]
    idx = [
        v for v in eqn.invars
        if v not in refs and isinstance(v, jax_core.Var)
    ]
    if len(refs) < 3:  # pragma: no cover - jax layout drift guard
        return None
    return refs[0], refs[1], refs[2], tuple(idx)


def _var_key(v) -> Tuple:
    if isinstance(v, jax_core.Var):
        return ("var", id(v))
    return ("lit", repr(getattr(v, "val", v)))


@dataclasses.dataclass
class KernelArtifact:
    """One traced pallas_call: the kernel jaxpr plus its grid metadata."""

    name: str
    target: str                   # registry target this was found under
    jaxpr: object                 # the kernel Jaxpr
    grid_mapping: object
    input_output_aliases: Tuple
    params: Dict

    # ---- operands -------------------------------------------------------
    def operands(self) -> List[KernelOperand]:
        gm = self.grid_mapping
        n_idx = gm.num_index_operands
        n_in = gm.num_inputs
        n_out = gm.num_outputs
        bms = list(gm.block_mappings)
        ops: List[KernelOperand] = []
        for i, var in enumerate(self.jaxpr.invars):
            if i < n_idx:
                role, bm = "index", None
            elif i < n_idx + n_in:
                role, bm = "input", bms[i - n_idx]
            elif i < n_idx + n_in + n_out:
                role, bm = "output", bms[i - n_idx]
            else:
                role, bm = "scratch", None
            ops.append(KernelOperand(
                index=i, role=role, space=_memory_space(var.aval),
                aval=var.aval, block_mapping=bm,
            ))
        return ops

    @property
    def grid(self) -> Tuple[int, ...]:
        return tuple(int(g) for g in self.grid_mapping.grid)

    # ---- DMA events -----------------------------------------------------
    def dma_events(self) -> List[DmaEvent]:
        events: List[DmaEvent] = []
        self._collect_dma(self.jaxpr, (), events)
        return events

    def _collect_dma(self, jaxpr, region: Tuple, events: List[DmaEvent],
                     base_pos: int = 0, env: Optional[Dict] = None) -> None:
        env = env or {}

        def resolve(v):
            # map sub-jaxpr invars back to the enclosing body's vars so a
            # DMA inside a cond branch still names the kernel's refs
            seen = set()
            while id(v) in env and id(v) not in seen:
                seen.add(id(v))
                v = env[id(v)]
            return v

        for pos, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            if name in ("dma_start", "dma_wait"):
                parts = _dma_refs(eqn)
                if parts is None:
                    continue
                src, dst, sem, idx = parts
                src, dst, sem = resolve(src), resolve(dst), resolve(sem)
                idx = tuple(resolve(v) for v in idx)
                events.append(DmaEvent(
                    kind="start" if name == "dma_start" else "wait",
                    key=(_var_key(sem), _var_key(src), _var_key(dst)),
                    position=base_pos + pos,
                    region=region,
                    src_space=_memory_space(src.aval),
                    dst_space=_memory_space(dst.aval),
                    src_var=src,
                    dst_var=dst,
                    index_vars=idx,
                ))
            elif name == "cond":
                # cond invars = [branch index, *operands]; each branch
                # jaxpr's invars bind the operands positionally
                operands = eqn.invars[1:]
                for b, sub in enumerate(_param_jaxprs(eqn)):
                    sub_env = dict(env)
                    for inner, outer in zip(sub.invars, operands):
                        sub_env[id(inner)] = outer
                    self._collect_dma(
                        sub, region + (("cond", base_pos + pos, b),),
                        events, base_pos + pos, sub_env,
                    )
            elif name in ("while", "scan", "pjit", "custom_jvp_call",
                          "custom_vjp_call", "checkpoint", "remat"):
                for sub in _param_jaxprs(eqn):
                    self._collect_dma(sub, region, events, base_pos + pos,
                                      env)

    # ---- provenance -----------------------------------------------------
    def scalar_source(self, var) -> Optional[int]:
        """If ``var`` is (transitively) a scalar read of an index-operand
        ref (scalar-prefetch SMEM), return that operand's position among
        the index operands; else None. Used to tell the u-block write-back
        from the v-block one in the boundary kernel."""
        n_idx = self.grid_mapping.num_index_operands
        idx_vars = {id(v): i for i, v in
                    enumerate(self.jaxpr.invars[:n_idx])}
        defs = {}
        for eqn in self.jaxpr.eqns:
            for out in eqn.outvars:
                defs[id(out)] = eqn
        seen = set()
        frontier = [var]
        while frontier:
            v = frontier.pop()
            if id(v) in seen or not isinstance(v, jax_core.Var):
                continue
            seen.add(id(v))
            eqn = defs.get(id(v))
            if eqn is None:
                continue
            if eqn.primitive.name == "get":
                ref = eqn.invars[0]
                if id(ref) in idx_vars:
                    return idx_vars[id(ref)]
            frontier.extend(eqn.invars)
        return None


def collect_pallas_calls(closed_jaxpr, target: str) -> List[KernelArtifact]:
    """Every pallas_call eqn reachable from ``closed_jaxpr``, wrapped."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    out: List[KernelArtifact] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        info = eqn.params.get("name_and_src_info")
        name = getattr(info, "name", None) or eqn.params.get("name", "kernel")
        out.append(KernelArtifact(
            name=str(name),
            target=target,
            jaxpr=eqn.params["jaxpr"],
            grid_mapping=eqn.params["grid_mapping"],
            input_output_aliases=tuple(
                eqn.params.get("input_output_aliases", ())
            ),
            params=eqn.params,
        ))
    return out


# --------------------------------------------------------------------------
# index-map evaluation (per-grid-step read/write sets)
# --------------------------------------------------------------------------

def eval_index_map(block_mapping, grid_point: Sequence[int]):
    """Evaluate a BlockSpec index map at one grid point; returns the block
    coordinate tuple, or None when the map needs runtime data (e.g. reads
    a scalar-prefetch ref) and cannot be enumerated statically."""
    cj = block_mapping.index_map_jaxpr
    n_extra = len(cj.jaxpr.invars) - len(grid_point)
    args = [jnp.int32(g) for g in grid_point]
    for var in cj.jaxpr.invars[len(grid_point):]:
        aval = var.aval
        shape = getattr(aval, "shape", ())
        dtype = getattr(aval, "dtype", jnp.int32)
        args.append(jnp.zeros(shape, dtype))
    if n_extra < 0:
        return None
    try:
        out = jax_core.jaxpr_as_fun(cj)(*args)
    except Exception:
        return None
    return tuple(int(x) for x in out)


def enumerate_grid(grid: Sequence[int], cap: int = 65536):
    """All grid points in execution order (last dim innermost), or None if
    the grid is bigger than ``cap`` steps (registry targets are small)."""
    total = int(np.prod(grid, dtype=np.int64)) if grid else 1
    if total > cap:
        return None
    pts = np.stack(
        np.meshgrid(*[np.arange(g) for g in grid], indexing="ij"), -1
    ).reshape(-1, len(grid)) if grid else np.zeros((1, 0), np.int64)
    return [tuple(int(x) for x in p) for p in pts]


# --------------------------------------------------------------------------
# liveness-based intermediate VMEM estimate
# --------------------------------------------------------------------------

def peak_live_bytes(jaxpr) -> int:
    """Upper-bound the peak bytes of live intermediate values in a kernel
    body: a linear scan with last-use liveness (classic register-pressure
    estimate). Sub-jaxprs (cond/while/pjit) contribute their own peak on
    top of the live set at their call site. Refs are excluded — they are
    counted from block shapes / scratch, not from the value graph."""
    last_use: Dict[int, int] = {}
    eqns = list(jaxpr.eqns)
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if isinstance(v, jax_core.Var):
                last_use[id(v)] = i
    for v in jaxpr.outvars:
        if isinstance(v, jax_core.Var):
            last_use[id(v)] = len(eqns)

    live: Dict[int, int] = {}
    cur = 0
    peak = 0
    for i, eqn in enumerate(eqns):
        sub_peak = 0
        for sub in _param_jaxprs(eqn):
            sub_peak = max(sub_peak, peak_live_bytes(sub))
        peak = max(peak, cur + sub_peak)
        for v in eqn.outvars:
            if isinstance(v, jax_core.Var) and not _is_ref(v):
                b = _aval_bytes(v.aval)
                if b and last_use.get(id(v), -1) > i:
                    live[id(v)] = b
                    cur += b
        peak = max(peak, cur)
        # retire values whose last use was this eqn
        for v in eqn.invars:
            if isinstance(v, jax_core.Var) and last_use.get(id(v)) == i:
                b = live.pop(id(v), 0)
                cur -= b
    return peak


def operand_vmem_bytes(op: KernelOperand) -> int:
    """Resident VMEM bytes one operand costs per grid step. Block-mapped
    refs are double-buffered by the pipeline (x2); VMEM scratch is single;
    ANY-space refs live in HBM (0); SMEM scalars are negligible but
    counted at face value; semaphores are free."""
    if op.space == "semaphore":
        return 0
    if op.space == "any":
        return 0
    if op.role == "scratch":
        return _aval_bytes(op.aval)
    if op.role == "index" or op.space == "smem":
        return _aval_bytes(op.aval)
    bs = op.block_shape
    if bs is None:
        return _aval_bytes(op.aval)
    itemsize = jnp.dtype(op.dtype).itemsize if op.dtype is not None else 1
    return 2 * int(np.prod(bs, dtype=np.int64)) * itemsize

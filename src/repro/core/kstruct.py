"""Kernel-interior structure recovery — ``hpcstruct`` for Pallas kernels
(paper §5 applied *inside* the GPU binary; §7 PC-sampling attribution).

The HLO-level structure parse (``repro.core.structure``) stops at op
granularity: a ``pl.pallas_call`` compiles to one opaque ``custom-call``
HLO op, so an entire flash-attention kernel gets exactly one context no
matter how hot its inner loops are.  HPCToolkit recovers kernel
interiors by disassembling the GPU binary (nvdisasm/Dyninst); our
"binary" for a Pallas kernel is the *kernel jaxpr* — the traced program
``pallas_call`` lowers, which carries per-equation ``source_info``:

- the user-frame traceback gives **source lines** and the **inlined
  scope chain** (``pl.when`` bodies and helper functions appear as
  nested frames, exactly the inline chains §5 recovers from DWARF);
- ``scan``/``while`` equations (``jax.lax.fori_loop``) and the
  sequential grid dimensions give the **loop nest**;
- equation avals give a per-leaf roofline weight (the PC-sampling
  descent weights) and a stall class (compute vs memory bound —
  THAPI-style classification, PAPERS.md).

``KernelStructure.from_function`` traces the kernel's host wrapper with
``jax.make_jaxpr`` and recovers a ``GPU_FUNC -> GPU_LOOP -> GPU_OP``
``Frame`` tree mirroring the HLO path's shapes.  ``structure.HloModule
.bind_kernel_structure`` attaches it to the matching ``custom-call``
ops; ``sampling.pc_samples`` then descends into bound ops, distributing
each op's samples over interior leaves (two-level draw, governor cap
preserved exactly); ``profiler._attribute`` splices the leaf frames
under the op's GPU context, so the interiors ride the canonical
database contract as ordinary tree paths (byte-deterministic through
``aggregate()``/``merge_databases`` — pinned in tests/test_kstruct.py).

Structures are plain data: hand-building one (tests, goldens, non-JAX
backends) needs only ``KernelLeaf`` tuples — tracing is just the
recovery front end.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.cct import Frame, GPU_FUNC, GPU_LOOP, GPU_OP
from repro.core.peaks import Peaks, device_peaks

# transcendental primitives get the same 10x element weight the HLO
# cost model uses (structure._estimate_costs)
_TRANSCENDENTAL = frozenset({
    "exp", "exp2", "expm1", "log", "log1p", "tanh", "rsqrt", "sqrt",
    "pow", "integer_pow", "logistic", "sin", "cos", "erf", "erf_inv"})

# Ref load/store primitives: the kernel's memory traffic analogue
_MEMORY = frozenset({"get", "swap", "masked_load", "masked_swap",
                     "load", "store"})

# never-sampled bookkeeping primitives (cf. sampling._NON_INST)
_NON_INST = frozenset({"program_id", "num_programs", "broadcast_in_dim",
                       "convert_element_type", "reshape", "squeeze",
                       "transpose"})


@dataclasses.dataclass(frozen=True)
class KernelLeaf:
    """One sampled 'instruction' inside a kernel: a (scope chain, source
    line) group of jaxpr equations."""
    frames: Tuple[Frame, ...]   # GPU_LOOP/GPU_FUNC chain + GPU_OP leaf
    weight: float               # modeled seconds (roofline max term)
    stall: str                  # "compute" | "memory"
    flops: float = 0.0
    bytes: float = 0.0

    @property
    def line(self) -> int:
        return self.frames[-1].line


class KernelStructure:
    """The kernel-interior analogue of ``structure.HloModule``: a
    GPU_FUNC root, loop/scope frames, and weighted GPU_OP leaves."""

    def __init__(self, name: str, file: str, line: int,
                 leaves: Sequence[KernelLeaf],
                 grid: Tuple[int, ...] = ()):
        self.name = name
        self.file = file
        self.line = line
        self.grid = tuple(grid)
        self.leaves: Tuple[KernelLeaf, ...] = tuple(leaves)
        self.root = Frame(GPU_FUNC, name, file, line)
        self._p: Optional[np.ndarray] = None

    def __repr__(self) -> str:
        return (f"KernelStructure({self.name!r}, {len(self.leaves)} "
                f"leaves, grid={self.grid})")

    # -- totals (the counter-collector refinement inputs) -----------------
    @property
    def total_flops(self) -> float:
        return sum(lf.flops for lf in self.leaves)

    @property
    def total_bytes(self) -> float:
        return sum(lf.bytes for lf in self.leaves)

    @property
    def active_s(self) -> float:
        return sum(lf.weight for lf in self.leaves)

    def leaf_frames(self, i: int) -> Tuple[Frame, ...]:
        """Full interior frame path for leaf ``i`` (root included) — what
        the profiler splices under the kernel's GPU_OP context."""
        return (self.root,) + self.leaves[i].frames

    # -- sample descent ---------------------------------------------------
    def leaf_p(self) -> np.ndarray:
        """Normalized leaf weights (cached — the descent runs on the
        dispatch path, cf. sampling._op_weights_cache)."""
        if self._p is None:
            w = np.asarray([lf.weight for lf in self.leaves], np.float64)
            total = w.sum()
            self._p = w / total if total > 0 else \
                np.full(len(w), 1.0 / max(len(w), 1))
        return self._p

    def distribute(self, count: int, rng=None) -> List[Tuple[int, int]]:
        """Apportion ``count`` samples over leaves; returns non-zero
        ``(leaf_index, count)`` pairs summing to exactly ``count`` (the
        governor's per-dispatch cap survives the descent unchanged).

        Deterministic mode uses largest-remainder apportionment (floor +
        remainder ranking), so the two-level draw is a pure function of
        (structure, count); with ``rng`` it is one multinomial."""
        if count <= 0 or not self.leaves:
            return []
        p = self.leaf_p()
        if rng is not None:
            counts = rng.multinomial(int(count), p)
        else:
            exact = count * p
            counts = np.floor(exact).astype(np.int64)
            short = int(count - counts.sum())
            if short > 0:
                # ties broken by leaf order: stable + deterministic
                order = np.argsort(-(exact - counts), kind="stable")
                counts[order[:short]] += 1
        return [(int(i), int(counts[i])) for i in np.nonzero(counts)[0]]

    # -- recovery front ends ---------------------------------------------
    @classmethod
    def from_function(cls, fn, *example_args, name: Optional[str] = None,
                      grid_loops: Optional[Dict[int, str]] = None,
                      **kwargs) -> "KernelStructure":
        """Trace ``fn(*example_args, **kwargs)`` (the host wrapper that
        issues the ``pallas_call``) and recover the first Pallas kernel
        found.  ``grid_loops`` names the *sequential* grid axes (TPU
        executes the grid in order; the scratch-carrying innermost axis
        is the kernel's outer loop), e.g. ``{4: "kv_blocks"}``."""
        import functools
        import jax
        closed = jax.make_jaxpr(functools.partial(fn, **kwargs))(
            *example_args)
        eqn = _find_pallas_call(closed.jaxpr)
        if eqn is None:
            raise ValueError(f"no pallas_call found tracing {fn!r}")
        return cls.from_pallas_eqn(eqn, name=name, grid_loops=grid_loops)

    @classmethod
    def from_pallas_eqn(cls, eqn, name: Optional[str] = None,
                        grid_loops: Optional[Dict[int, str]] = None
                        ) -> "KernelStructure":
        """Recover from one ``pallas_call`` equation of an outer jaxpr."""
        inner = eqn.params["jaxpr"]
        kname, kfile, kline = _kernel_ident(eqn, inner)
        name = name or kname
        base = os.path.basename(kfile)
        grid = tuple(int(g) for g in
                     getattr(eqn.params.get("grid_mapping"), "grid", ()) or ())
        # sequential grid axes become the outermost loop frames
        loop_prefix: Tuple[Frame, ...] = tuple(
            Frame(GPU_LOOP, f"grid:{gname}", base, kline)
            for _, gname in sorted((grid_loops or {}).items()))
        acc = _LeafAccumulator(kname, kfile, base, device_peaks())
        _walk_jaxpr(inner, acc, loop_prefix, 1.0)
        return cls(name, base, kline, acc.build(), grid=grid)


# --------------------------------------------------------------------------
# jaxpr walk
# --------------------------------------------------------------------------
def _find_pallas_call(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", None)
            if sub is not None:
                found = _find_pallas_call(sub)
                if found is not None:
                    return found
    return None


def _kernel_ident(eqn, inner) -> Tuple[str, str, int]:
    """(function name, file, def line) of the kernel callable.  The
    kernel jaxpr's debug info names the traced function; an explicit
    ``pallas_call(name=...)`` is only a label and never a frame."""
    kname = inner.debug_info.func_name
    for e in inner.eqns:
        frames = _user_frames(e)
        for fr in frames:
            if fr.function_name == kname:
                return kname, fr.file_name, int(fr.start_line)
        if frames:   # name didn't match any frame: innermost file wins
            return kname, frames[0].file_name, int(frames[0].start_line)
    return kname, "?", 0


def _user_frames(eqn):
    from jax._src import source_info_util
    return list(source_info_util.user_frames(eqn.source_info.traceback))


def _aval_elems(aval) -> int:
    shape = getattr(aval, "shape", ())
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _aval_bytes(aval) -> int:
    dt = getattr(aval, "dtype", None)
    return _aval_elems(aval) * (dt.itemsize if dt is not None else 4)


def _eqn_costs(eqn) -> Tuple[float, float]:
    """(flops, bytes) roofline estimate for one kernel equation —
    mirrors structure._estimate_costs at jaxpr granularity."""
    prim = eqn.primitive.name
    out_elems = sum(_aval_elems(v.aval) for v in eqn.outvars)
    if prim in _MEMORY:
        moved = max(sum(_aval_bytes(v.aval) for v in eqn.outvars),
                    max((_aval_bytes(v.aval) for v in eqn.invars
                         if hasattr(v, "aval")), default=0))
        return 0.0, float(moved)
    if prim == "dot_general":
        ((lc, _), _) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        k = 1
        for d in lc:
            k *= int(lhs.shape[d])
        return 2.0 * out_elems * k, 0.0
    if prim in _TRANSCENDENTAL:
        return 10.0 * out_elems, 0.0
    if prim.startswith("reduce_") or prim.startswith("cum"):
        in_elems = sum(_aval_elems(v.aval) for v in eqn.invars
                       if hasattr(v, "aval"))
        return float(in_elems), 0.0
    if prim in _NON_INST:
        return 0.0, 0.0
    return float(out_elems), 0.0


class _LeafAccumulator:
    """Groups equations by (loop chain, inline scope chain, source line)
    into deterministic, first-occurrence-ordered leaves."""

    def __init__(self, kernel_fn: str, kernel_file: str, base: str,
                 peaks: Peaks):
        self.kernel_fn = kernel_fn
        self.peaks = peaks
        self.kernel_file = kernel_file
        self.base = base
        self._groups: Dict[tuple, dict] = {}

    def _scopes_and_line(self, eqn) -> Tuple[Tuple[Frame, ...], int]:
        frames = _user_frames(eqn)
        # innermost-first; keep the chain inside the kernel function
        chain = []
        for fr in frames:
            if fr.function_name == self.kernel_fn:
                break
            if fr.file_name != self.kernel_file:
                break
            chain.append(fr)
        line = int(frames[0].start_line) if frames else 0
        scopes = []
        for i, fr in enumerate(reversed(chain)):     # outermost first
            outer = chain[len(chain) - i] if len(chain) - i < len(chain) \
                else None
            # scope frame line = the call site in the enclosing frame
            site = int(frames[len(chain) - i].start_line) \
                if len(chain) - i < len(frames) else int(fr.start_line)
            # frames carry qualified names ("kernel.<locals>._block");
            # the scope is named by the function itself
            name = fr.function_name.rsplit(".", 1)[-1]
            scopes.append(Frame(GPU_FUNC, name, self.base, site))
        return tuple(scopes), line

    def add(self, eqn, loops: Tuple[Frame, ...], trip: float) -> None:
        flops, nbytes = _eqn_costs(eqn)
        prim = eqn.primitive.name
        if prim in _NON_INST and flops == 0.0 and nbytes == 0.0:
            return
        scopes, line = self._scopes_and_line(eqn)
        key = (loops, scopes, line)
        g = self._groups.get(key)
        if g is None:
            g = self._groups[key] = {
                "order": len(self._groups), "flops": 0.0, "bytes": 0.0,
                "prims": {}}
        g["flops"] += flops * trip
        g["bytes"] += nbytes * trip
        w = max(flops / self.peaks.flops, nbytes / self.peaks.vmem_bw)
        g["prims"][prim] = g["prims"].get(prim, 0.0) + w

    def build(self) -> List[KernelLeaf]:
        leaves = []
        for (loops, scopes, line), g in sorted(
                self._groups.items(), key=lambda kv: kv[1]["order"]):
            # dominant primitive names the leaf (ties: alphabetical)
            dom = max(sorted(g["prims"]), key=lambda p: g["prims"][p])
            t_c = g["flops"] / self.peaks.flops
            t_m = g["bytes"] / self.peaks.vmem_bw
            weight = max(t_c, t_m, 1.0 / self.peaks.flops)
            leaf = Frame(GPU_OP, dom, self.base, line)
            leaves.append(KernelLeaf(
                frames=loops + scopes + (leaf,), weight=weight,
                stall="memory" if t_m > t_c else "compute",
                flops=g["flops"], bytes=g["bytes"]))
        return leaves


def _walk_jaxpr(jaxpr, acc: _LeafAccumulator, loops: Tuple[Frame, ...],
                trip: float) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "cond":
            # pl.when / lax.cond: branch bodies keep the current loop
            # chain; the branch function appears as an inline scope via
            # its traceback frames
            for br in eqn.params["branches"]:
                _walk_jaxpr(br.jaxpr, acc, loops, trip)
            continue
        if prim == "scan":
            length = float(eqn.params.get("length", 1) or 1)
            frames = _user_frames(eqn)
            line = int(frames[0].start_line) if frames else 0
            lf = Frame(GPU_LOOP, f"loop@{line}", acc.base, line)
            _walk_jaxpr(eqn.params["jaxpr"].jaxpr, acc, loops + (lf,),
                        trip * length)
            continue
        if prim == "while":
            frames = _user_frames(eqn)
            line = int(frames[0].start_line) if frames else 0
            lf = Frame(GPU_LOOP, f"loop@{line}", acc.base, line)
            # trip count is dynamic; leaves keep the loop frame, weight
            # scales by 1 (cf. structure.loop_depth's static chains)
            _walk_jaxpr(eqn.params["body_jaxpr"].jaxpr, acc, loops + (lf,),
                        trip)
            continue
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if sub is not None and prim != "pallas_call":
            _walk_jaxpr(getattr(sub, "jaxpr", sub), acc, loops, trip)
            continue
        acc.add(eqn, loops, trip)

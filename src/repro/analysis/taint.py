"""Level-1 flcheck: jaxpr dataflow taint for the federated round bodies.

**The contract being proved** (paper privacy pitch; docs/privacy.md): a
per-client update delta may only cross a shard boundary — any cross-client
collective or the vmap path's cross-client reduction — after flowing through
EVERY transform stage the config enables (clip -> noise -> quantize ->
mask).  Numeric tests pin that the configured pipeline currently behaves;
this pass proves the dataflow *structurally*, per config, on the actual
round body jaxpr — so a refactor that silently moves the masking after the
psum (or drops a stage on one topology) fails CI even if no numeric pin
happens to cover that path.

**How**: the production pipeline carries three zero-cost markers —

* :func:`tag_private` at the delta's birth (``fedavg._pipeline_body``),
* :func:`declassify` at each transform stage's output
  (``core/transforms.py``, ``core/secure_agg.py``), labeled ``clip`` /
  ``noise`` / ``quantize`` / ``mask``,
* :func:`boundary` on every aggregator's reduction input
  (``core/aggregation.py``) — the semantic "this value leaves the client
  shard" point, which also covers the vmap path where no collective
  primitive exists.

In production the markers are plain identity returns (no primitive is
bound; zero trace or runtime cost).  Under :func:`analysis_mode` they bind
identity primitives that appear in the jaxpr, and :func:`analyze_closed`
interprets the jaxpr abstractly: a value is *tainted* when it descends from
a ``tag_private`` source; passing a ``declassify`` adds its label; reaching
a ``boundary`` or a raw collective (``psum`` & friends, defense-in-depth)
with any required label missing is a violation.  Taint joins as you expect
(labels = intersection over tainted operands: mixing a masked and an
unmasked delta is only as sanitized as the weaker one), and the interpreter
descends into pjit / shard_map / scan / while / cond / custom-vjp
sub-jaxprs (scan/while to a fixpoint).

**What this does and does not prove** — see ``docs/static_analysis.md``:
it proves marker placement relative to boundaries on the traced dataflow,
for the traced config and topology; it does not prove the transforms'
numerics (the tests pin those) nor cover values never tagged (e.g. the
weighted scalar LOSS reduction, an accepted disclosure documented in
docs/privacy.md).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, FrozenSet, List, Optional, Sequence

import jax

PyTree = Any

# --------------------------------------------------------------- markers
_ANALYSIS_MODE = False

try:  # jax >= 0.4.33 keeps Primitive in jax.extend.core
    from jax.extend.core import Primitive as _Primitive
except ImportError:  # pragma: no cover - older jax
    from jax.core import Primitive as _Primitive  # type: ignore

from jax.interpreters import batching as _batching
from jax.interpreters import mlir as _mlir


def _identity_prim(name: str) -> _Primitive:
    p = _Primitive(name)
    p.def_impl(lambda x, **kw: x)
    p.def_abstract_eval(lambda aval, **kw: aval)
    _batching.defvectorized(p)           # vmap: rebind on the batched value
    try:  # identity lowering so a leaked marker can never break a compile
        _mlir.register_lowering(p, lambda ctx, x, **kw: [x])
    except Exception:  # pragma: no cover - lowering registry moved
        pass
    return p


source_p = _identity_prim("flcheck_source")
declassify_p = _identity_prim("flcheck_declassify")
boundary_p = _identity_prim("flcheck_boundary")


class analysis_mode:
    """Context manager: make the pipeline's taint markers bind real (still
    identity) primitives so they appear in traced jaxprs.  Production code
    never enters this, so the markers cost nothing there."""

    def __enter__(self):
        global _ANALYSIS_MODE
        self._prev = _ANALYSIS_MODE
        _ANALYSIS_MODE = True
        return self

    def __exit__(self, *exc):
        global _ANALYSIS_MODE
        _ANALYSIS_MODE = self._prev
        return False


def tag_private(tree: PyTree) -> PyTree:
    """Mark a per-client value tree as the private taint source."""
    if not _ANALYSIS_MODE:
        return tree
    return jax.tree.map(lambda x: source_p.bind(x), tree)


def declassify(tree: PyTree, label: str,
               wire: Optional[str] = None) -> PyTree:
    """Record that ``tree`` passed the transform stage ``label``.

    ``wire`` optionally declares the WIRE ENCODING the stage leaves the
    upload in (``"int8+scale"`` for the quantizer's int grid + per-leaf
    fp32 scale, ``"float32"`` for a stage that re-widens, e.g. the float
    pairwise masks).  The level-3 cost auditor (``analysis/costs.py``)
    reads the declaration off the boundary crossings; stages that do not
    change the encoding pass ``wire=None`` and the value keeps whatever
    encoding it already carried (``None`` = raw fp32)."""
    if not _ANALYSIS_MODE:
        return tree
    return jax.tree.map(
        lambda x: declassify_p.bind(x, label=label, wire=wire), tree)


def boundary(tree: PyTree) -> PyTree:
    """Mark a shard-boundary crossing point (aggregator reductions, or the
    semi-sync path's per-client uploads leaving the round body)."""
    if not _ANALYSIS_MODE:
        return tree
    return jax.tree.map(lambda x: boundary_p.bind(x), tree)


# ------------------------------------------------------------ interpreter
# cross-shard collectives checked in addition to the boundary markers
COLLECTIVES = frozenset({
    "psum", "psum2", "pmean", "pmax", "pmin", "all_gather",
    "all_gather_invariant", "all_to_all", "reduce_scatter", "ppermute",
    "pbroadcast",
})


@dataclasses.dataclass(frozen=True)
class Taint:
    """Labels of the sanitizer stages this value has passed through, plus
    the declared wire encoding (``None`` = undeclared, i.e. raw fp32)."""
    labels: FrozenSet[str]
    wire: Optional[str] = None


TaintVal = Optional[Taint]  # None = clean (no private ancestry)


def _wire_rank(wire: Optional[str]) -> int:
    """Width order for joining wire declarations: an ``int<k>+scale`` grid
    is narrower than an undeclared/float32 payload; mixing always widens to
    the widest ancestor (conservative: a sum of an int8 grid with anything
    wider no longer fits the grid)."""
    if wire and wire.startswith("int") and wire.endswith("+scale"):
        try:
            return int(wire[3:-len("+scale")])
        except ValueError:  # pragma: no cover - malformed declaration
            return 1 << 10
    return 1 << 10                       # None / "float32" / unknown: widest


def _join_wire(a: Optional[str], b: Optional[str]) -> Optional[str]:
    return a if _wire_rank(a) >= _wire_rank(b) else b


@dataclasses.dataclass(frozen=True)
class Crossing:
    """One boundary/collective equation observed by the interpreter — the
    raw material of the level-3 cost audit (``analysis/costs.py``):
    primitive name, operand shape/dtype, and (for tainted operands) the
    joined sanitizer labels + declared wire encoding."""
    primitive: str
    shape: tuple
    dtype: str
    tainted: bool
    labels: Optional[FrozenSet[str]] = None
    wire: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class TaintViolation:
    primitive: str
    missing: FrozenSet[str]
    applied: FrozenSet[str]

    def render(self) -> str:
        return (f"tainted value reaches {self.primitive} with stages "
                f"{sorted(self.applied)} applied but "
                f"{sorted(self.missing)} missing")


@dataclasses.dataclass
class TaintReport:
    required: FrozenSet[str]
    violations: List[TaintViolation]
    checked: int       # boundary/collective eqns that saw a tainted operand
    sources: int       # tag_private markers found in the jaxpr
    # every boundary/collective crossing observed (tainted or not), in eqn
    # order — consumed by the level-3 cost auditor (analysis/costs.py)
    crossings: List[Crossing] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    @property
    def proved(self) -> bool:
        """True when the pass actually proved something: the private source
        was present, at least one tainted value crossed a checked boundary,
        and every crossing carried every required stage label."""
        return self.ok and self.sources > 0 and self.checked > 0

    def render(self) -> str:
        state = ("PROVED" if self.proved
                 else ("VACUOUS" if self.ok else "VIOLATED"))
        head = (f"taint {state}: required={sorted(self.required)} "
                f"sources={self.sources} tainted-crossings={self.checked}")
        return "\n".join([head] + ["  " + v.render()
                                   for v in self.violations])


def _join(taints: Sequence[TaintVal]) -> TaintVal:
    """Combine operand taints: tainted if ANY is; labels = intersection over
    the tainted ones (mixing weakens to the least-sanitized ancestor); the
    wire encoding widens to the widest tainted ancestor."""
    labels: Optional[FrozenSet[str]] = None
    wire: Optional[str] = None
    first = True
    for t in taints:
        if t is not None:
            if labels is None:
                labels = t.labels
            else:
                labels = labels & t.labels
            wire = t.wire if first else _join_wire(wire, t.wire)
            first = False
    return None if labels is None else Taint(labels, wire)


def _taint_eq(a: TaintVal, b: TaintVal) -> bool:
    return (a is None) == (b is None) and \
        (a is None or (a.labels == b.labels and a.wire == b.wire))


def _merge(old: TaintVal, new: TaintVal) -> TaintVal:
    """Fixpoint accumulator: taint only grows, labels only shrink."""
    return _join([old, new]) if (old is not None or new is not None) else None


def _sub_jaxprs(params: Dict[str, Any]):
    """Every (Closed)Jaxpr reachable in an eqn's params, with its key."""
    from jax._src import core as jcore
    found = []
    for k, v in params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                found.append((k, item))
    return found


def _as_open(j):
    """(jaxpr, const_taints) view of a Jaxpr or ClosedJaxpr."""
    if hasattr(j, "jaxpr"):
        return j.jaxpr, [None] * len(j.consts)
    return j, []


class _Interp:
    def __init__(self, required: FrozenSet[str]):
        self.required = required
        self.violations: List[TaintViolation] = []
        self.checked = 0
        self.sources = 0
        self.crossings: List[Crossing] = []

    def _check(self, eqn, taints: Sequence[TaintVal]) -> None:
        prim = eqn.primitive.name
        joined = _join(taints)
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            self.crossings.append(Crossing(
                prim, tuple(aval.shape), str(aval.dtype),
                joined is not None,
                None if joined is None else joined.labels,
                None if joined is None else joined.wire))
        if joined is None:
            return
        self.checked += 1
        missing = self.required - joined.labels
        if missing:
            self.violations.append(
                TaintViolation(prim, frozenset(missing), joined.labels))

    def run(self, jaxpr, in_taints: Sequence[TaintVal],
            const_taints: Sequence[TaintVal] = ()) -> List[TaintVal]:
        env: Dict[Any, TaintVal] = {}

        def read(v) -> TaintVal:
            return None if type(v).__name__ == "Literal" else env.get(v)

        for var, t in list(zip(jaxpr.constvars, const_taints)) + \
                list(zip(jaxpr.invars, in_taints)):
            env[var] = t
        for eqn in jaxpr.eqns:
            in_t = [read(v) for v in eqn.invars]
            out_t = self._eqn(eqn, in_t)
            for var, t in zip(eqn.outvars, out_t):
                env[var] = t
        return [read(v) for v in jaxpr.outvars]

    # ------------------------------------------------------------- eqns
    def _eqn(self, eqn, in_t: List[TaintVal]) -> List[TaintVal]:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)
        if name == "flcheck_source":
            self.sources += 1
            return [Taint(frozenset())]
        if name == "flcheck_declassify":
            t = in_t[0]
            label = eqn.params["label"]
            wire = eqn.params.get("wire")
            if t is None:
                return [None]
            return [Taint(t.labels | {label},
                          t.wire if wire is None else wire)]
        if name == "flcheck_boundary":
            self._check(eqn, in_t)
            return [_join(in_t)]
        if name in COLLECTIVES:
            self._check(eqn, in_t)
            return [_join(in_t)] * n_out
        if name == "scan":
            return self._scan(eqn, in_t)
        if name == "while":
            return self._while(eqn, in_t)
        if name == "cond":
            return self._cond(eqn, in_t)
        subs = _sub_jaxprs(eqn.params)
        if subs:
            return self._call_like(eqn, in_t, subs)
        return [_join(in_t)] * n_out

    def _positional(self, sub, in_t: List[TaintVal],
                    n_out: int) -> List[TaintVal]:
        jx, const_t = _as_open(sub)
        if len(jx.invars) == len(in_t):
            sub_in = in_t
        else:  # unknown calling convention: weakest taint everywhere
            sub_in = [_join(in_t)] * len(jx.invars)
        out = self.run(jx, sub_in, const_t)
        if len(out) == n_out:
            return out
        return [_join(out + in_t)] * n_out

    def _call_like(self, eqn, in_t, subs) -> List[TaintVal]:
        n_out = len(eqn.outvars)
        outs = [self._positional(sub, in_t, n_out) for _, sub in subs]
        if len(outs) == 1:
            return outs[0]
        return [_join([o[i] for o in outs]) for i in range(n_out)]

    def _scan(self, eqn, in_t) -> List[TaintVal]:
        jx, const_t = _as_open(eqn.params["jaxpr"])
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        consts, carry, xs = in_t[:nc], in_t[nc:nc + ncar], in_t[nc + ncar:]
        for _ in range(32):  # taint lattice is tiny: converges fast
            out = self.run(jx, consts + carry + xs, const_t)
            new_carry = [_merge(c, o) for c, o in zip(carry, out[:ncar])]
            if all(_taint_eq(a, b) for a, b in zip(carry, new_carry)):
                break
            carry = new_carry
        out = self.run(jx, consts + carry + xs, const_t)
        return out

    def _while(self, eqn, in_t) -> List[TaintVal]:
        cj, cj_const = _as_open(eqn.params["cond_jaxpr"])
        bj, bj_const = _as_open(eqn.params["body_jaxpr"])
        cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
        cond_c, body_c, carry = in_t[:cn], in_t[cn:cn + bn], in_t[cn + bn:]
        for _ in range(32):
            out = self.run(bj, body_c + carry, bj_const)
            new_carry = [_merge(c, o) for c, o in zip(carry, out)]
            if all(_taint_eq(a, b) for a, b in zip(carry, new_carry)):
                break
            carry = new_carry
        self.run(cj, cond_c + carry, cj_const)   # cond may contain checks
        return carry

    def _cond(self, eqn, in_t) -> List[TaintVal]:
        n_out = len(eqn.outvars)
        outs = [self._positional(br, in_t[1:], n_out)
                for br in eqn.params["branches"]]
        return [_join([o[i] for o in outs]) for i in range(n_out)]


def analyze_closed(closed_jaxpr, required: FrozenSet[str],
                   in_taints: Optional[Sequence[TaintVal]] = None
                   ) -> TaintReport:
    """Interpret a ClosedJaxpr and check the sanitize-before-boundary
    contract for the given required stage labels."""
    interp = _Interp(frozenset(required))
    jx, const_t = _as_open(closed_jaxpr)
    if in_taints is None:
        in_taints = [None] * len(jx.invars)
    interp.run(jx, list(in_taints), const_t)
    return TaintReport(frozenset(required), interp.violations,
                       interp.checked, interp.sources, interp.crossings)


# -------------------------------------------------------- pipeline proofs
def required_labels(tcfg, scfg=None) -> FrozenSet[str]:
    """The stage labels a config demands on every boundary crossing."""
    req = set()
    if tcfg.clip_norm > 0.0:
        req.add("clip")
    if tcfg.noise_multiplier > 0.0:
        req.add("noise")
    if tcfg.quantize_bits:
        req.add("quantize")
    if scfg is not None and scfg.enabled:
        req.add("mask")
    return frozenset(req)


def _round_shapes(fcfg, m: int, n_win: int = 4, steps: int = 2,
                  batch: int = 2):
    import jax.numpy as jnp

    from repro.models.forecaster import init_forecaster

    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: init_forecaster(
        jax.random.PRNGKey(0), fcfg))  # flcheck: disable=FLC001 (shape-only eval_shape stand-in; bits never materialize)
    x = sds((m, n_win, fcfg.lookback, 1), jnp.float32)
    y = sds((m, n_win, fcfg.horizon), jnp.float32)
    bidx = sds((m, steps, batch), jnp.int32)
    w = sds((m,), jnp.float32)
    keys = sds((m, 2), jnp.uint32)
    slots = sds((m,), jnp.int32)
    rk = sds((2,), jnp.uint32)
    lr = sds((), jnp.float32)
    mu = sds((), jnp.float32)
    return params, x, y, bidx, w, keys, slots, rk, lr, mu


def _maybe_analysis(analysis: bool):
    import contextlib
    return analysis_mode() if analysis else contextlib.nullcontext()


def trace_pipeline_round(fcfg, tcfg, scfg=None, acfg=None, mesh=None,
                         m: Optional[int] = None, analysis: bool = True):
    """Trace the REAL round body (vmap or mesh path) to a ClosedJaxpr with
    the taint markers active.

    Deliberately bypasses both jit caches (``pipeline_round.__wrapped__``,
    ``make_pipeline_round.__wrapped__``): a cached trace from a production
    (marker-free) run must never satisfy — or pollute — the analysis.

    ``analysis=False`` traces the PRODUCTION jaxpr (markers are no-ops, so
    they contribute zero equations) — what the level-3 FLOP/byte cost model
    walks, so marker bookkeeping can never pollute the cost numbers.
    """
    from repro.core import fedavg, losses
    from repro.configs.base import AggregationConfig

    from repro.core import transforms as transforms_mod

    loss = losses.make_loss("mse")
    # the extended (slots, w_full, round_key) call shape mirrors
    # fedavg.make_pipeline_round's own needs_cohort branch — the clear ring
    # quantizer (quantize_ring, no masker) is cohort-aware too
    needs_ctx = transforms_mod.make_stack(tcfg, scfg).needs_cohort
    if mesh is None:
        m = m or 4
        params, x, y, bidx, w, keys, slots, rk, lr, mu = _round_shapes(
            fcfg, m)
        body = getattr(fedavg.pipeline_round, "__wrapped__",
                       fedavg.pipeline_round)

        def entry(params, x, y, bidx, w, keys, rk, lr, mu):
            return body(params, x, y, bidx, w, keys, lr, mu, fcfg, loss,
                        tcfg, scfg, rk if needs_ctx else None)

        with _maybe_analysis(analysis):
            return jax.make_jaxpr(entry)(params, x, y, bidx, w, keys, rk,
                                         lr, mu)

    n_dev = 1
    for a in mesh.axis_names:
        n_dev *= mesh.shape[a]
    m = m or n_dev
    acfg = acfg or AggregationConfig()
    params, x, y, bidx, w, keys, slots, rk, lr, mu = _round_shapes(fcfg, m)
    with _maybe_analysis(analysis):
        # fresh (uncached) jitted round: lru_cache bypassed on purpose
        fn = fedavg.make_pipeline_round.__wrapped__(
            mesh, fcfg, loss, tcfg, acfg, scfg)
        if needs_ctx:
            return jax.make_jaxpr(fn)(params, x, y, bidx, w, keys, slots,
                                      w, rk, lr, mu)
        return jax.make_jaxpr(fn)(params, x, y, bidx, w, keys, lr, mu)


def trace_client_deltas(fcfg, tcfg, scfg=None, m: int = 4,
                        analysis: bool = True):
    """Trace the semi-sync dispatch stage (``async_engine.client_deltas``)
    — the boundary there is the function's RETURN (the buffered uploads)."""
    from repro.core import async_engine, losses
    from repro.core import transforms as transforms_mod

    loss = losses.make_loss("mse")
    needs_ctx = transforms_mod.make_stack(tcfg, scfg).needs_cohort
    params, x, y, bidx, w, keys, slots, rk, lr, mu = _round_shapes(fcfg, m)
    body = getattr(async_engine.client_deltas, "__wrapped__",
                   async_engine.client_deltas)

    def entry(params, x, y, bidx, w, keys, rk, lr, mu):
        return body(params, x, y, bidx, keys, lr, mu, fcfg, loss, tcfg,
                    scfg, rk if needs_ctx else None,
                    w if needs_ctx else None, None)

    with _maybe_analysis(analysis):
        return jax.make_jaxpr(entry)(params, x, y, bidx, w, keys, rk, lr,
                                     mu)


def verify_pipeline(topology: str, tcfg, scfg=None,
                    fcfg=None) -> TaintReport:
    """Prove sanitize-before-boundary for one topology x config.

    ``topology``: ``"vmap"`` (LocalAggregator — the boundary marker is the
    cross-client reduction), ``"flat"`` (1-D clients mesh over all
    devices), ``"hier"`` (2-D (region, clients) mesh, 2 regions), or
    ``"semi_sync"`` (the dispatch stage whose returned uploads feed the
    server's straggler buffer).
    """
    from repro.configs.base import AggregationConfig, ForecasterConfig

    fcfg = fcfg or ForecasterConfig(hidden_dim=8)
    req = required_labels(tcfg, scfg)
    if topology == "semi_sync":
        jx = trace_client_deltas(fcfg, tcfg, scfg)
    elif topology == "vmap":
        jx = trace_pipeline_round(fcfg, tcfg, scfg)
    elif topology == "flat":
        mesh = jax.make_mesh((len(jax.devices()),), ("clients",))
        jx = trace_pipeline_round(fcfg, tcfg, scfg, mesh=mesh)
    elif topology == "hier":
        n_dev = len(jax.devices())
        if n_dev % 2:
            raise ValueError(f"hier topology needs an even device count, "
                             f"got {n_dev}")
        mesh = jax.make_mesh((2, n_dev // 2), ("region", "clients"))
        jx = trace_pipeline_round(
            fcfg, tcfg, scfg, mesh=mesh,
            acfg=AggregationConfig(kind="hierarchical", n_regions=2))
    else:
        raise ValueError(f"unknown topology {topology!r} "
                         "(vmap | flat | hier | semi_sync)")
    return analyze_closed(jx, req)

"""Jaxpr auditor: dtype, callback, and SPMD-collective contracts.

Traces the central algorithms' ``_round_jit`` and fused-scan entry
points with ``jax.make_jaxpr`` on tiny synthetic shapes (trace only —
no training compute; CPU-safe on the 8-virtual-device test mesh) and
checks the contracts the runtime tests can only sample:

* **dtype whitelist** — no f64 promotion anywhere in the round jaxpr.
  The TPU-native dtype set is f32/bf16/i8/i32/u32/bool (+ PRNG key
  dtypes); a stray Python float or np scalar that promotes under x64
  doubles wire and HBM cost silently.
* **no host callbacks on the hot path** — ``pure_callback`` /
  ``io_callback`` / ``debug_callback`` primitives serialize the round
  against the host; the fused-scan design exists to remove exactly
  that.
* **collective consistency** — the SPMD race-detector analog this
  codebase needs: the multiset of collective primitives (``psum`` /
  ``psum_invariant``, ``all_gather``, ``ppermute``, ``reduce_scatter``, ...)
  with their axis names must be (a) identical between the fused and
  unfused round programs and (b) identical across the branches of
  every ``lax.cond`` (the guard's clean/quarantine split, watchdog
  retry gating). A branch-dependent collective deadlocks real
  multi-host SPMD — the exact hazard the PR-2 recovery docs flag as
  "per-process retry would break SPMD collective matching". On the
  CPU sim every process traces both branches identically, so only a
  static check can see the divergence before pod hardware does.
* **donation audit + gate** — every jit entry point with its
  ``donate_argnums`` status and per-call realloc bytes. Since the
  Round-14 ownership refactor the central entry points DONATE their
  input state (``donate_state``, on by default in the CLI): the audit
  instance is built donating, a donated entry's realloc drops from
  the full ``(1+C)``-model state to the trained slice (global +
  ``clients_per_round`` rows of each stacked field), and the entries
  pinned in ``results/lint_baseline.json``'s ``donated_entry_points``
  are GATED — a regression to un-donated is a ``jaxpr-donation``
  finding (exit 1). ``--jaxpr-no-donate`` (seeded-violation plumbing)
  audits a borrowing instance to prove the gate fires.
"""
from __future__ import annotations

import contextlib
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .findings import Finding

#: explicit collective primitives, as jax 0.9 names them in a traced
#: jaxpr: under shard_map's varying-axes check (``check_vma``, the default)
#: a psum / all_gather whose result is replicated is traced as the
#: ``*_invariant`` primitive; with the check off the plain names appear
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "all_gather", "all_gather_invariant",
    "all_gather_reduced", "all_to_all", "ppermute", "reduce_scatter",
    "pmin", "pmax", "pgather", "pbroadcast",
})

#: dtypes legal on the round hot path (str(aval.dtype)); PRNG key
#: dtypes (``key<fry>`` etc.) are matched by prefix
DTYPE_WHITELIST = frozenset({
    "float32", "bfloat16", "int8", "int32", "uint32", "bool",
    "float0",  # jax's zero-tangent marker, never materialized
})


def _dtype_ok(d: str) -> bool:
    return d in DTYPE_WHITELIST or d.startswith("key<")


class JaxprSummary:
    """Recursive walk of one traced program."""

    def __init__(self) -> None:
        self.collectives: Counter = Counter()   # (prim, axes) -> count
        self.dtypes: Dict[str, str] = {}        # dtype -> first path
        self.callbacks: List[Tuple[str, str]] = []
        self.cond_mismatches: List[Tuple[str, List[dict]]] = []

    @staticmethod
    def _axes_key(eqn) -> str:
        axes = eqn.params.get("axes",
                              eqn.params.get("axis_name", ()))
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        key = ",".join(str(a) for a in axes)
        if eqn.params.get("axis_index_groups") is not None:
            key += "|grouped"
        return key

    @staticmethod
    def _sub_jaxprs(eqn):
        for name, v in eqn.params.items():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for item in vals:
                # ClosedJaxpr first: it forwards .eqns, so the order
                # matters (unwrapping gets the invars/outvars too)
                if hasattr(item, "jaxpr") and \
                        hasattr(item.jaxpr, "eqns"):  # ClosedJaxpr
                    yield name, item.jaxpr
                elif hasattr(item, "eqns"):           # core.Jaxpr
                    yield name, item

    def _record_dtypes(self, jaxpr, path: str) -> None:
        for v in list(jaxpr.invars) + list(jaxpr.constvars) + \
                list(jaxpr.outvars):
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None:
                self.dtypes.setdefault(str(dt), path)

    def walk(self, jaxpr, path: str = "") -> Counter:
        """Returns this subtree's collective multiset (used by the
        cond-branch comparison) while accumulating globals."""
        local: Counter = Counter()
        self._record_dtypes(jaxpr, path)
        for eqn in jaxpr.eqns:
            nm = eqn.primitive.name
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None:
                    self.dtypes.setdefault(str(dt), f"{path}/{nm}")
            if nm in COLLECTIVE_PRIMS:
                local[(nm, self._axes_key(eqn))] += 1
            if "callback" in nm:
                self.callbacks.append((nm, path))
            if nm == "cond":
                branches: List[Counter] = []
                for sub_name, sub in self._sub_jaxprs(eqn):
                    branches.append(self.walk(
                        sub, f"{path}/cond.{sub_name}"))
                sigs = {tuple(sorted(b.items())) for b in branches}
                if len(sigs) > 1:
                    self.cond_mismatches.append(
                        (path or "<top>",
                         [dict(b) for b in branches]))
                for b in branches:
                    local.update(b)
            else:
                for sub_name, sub in self._sub_jaxprs(eqn):
                    local.update(self.walk(
                        sub, f"{path}/{nm}.{sub_name}"))
        return local

    def collective_multiset(self) -> Dict[str, int]:
        total: Counter = Counter()
        # note: cond branches were verified identical (or reported),
        # so counting every branch once each is the per-execution
        # multiset scaled by branch count — equal across programs with
        # equal structure, which is what the parity check compares
        return {f"{p}@{a}": c
                for (p, a), c in sorted(self.collectives.items())}


def summarize(fn: Callable, *args, x64: bool = False) -> JaxprSummary:
    """Trace ``fn(*args)`` (no compute) and summarize its jaxpr.

    ``x64=True`` traces under ``jax.enable_x64`` so latent
    f64 promotions (Python floats, np scalars) surface as f64 in the
    jaxpr instead of being silently demoted by the global x64-off
    default — the mode the seeded-violation fixtures run in."""
    import jax

    ctx = jax.enable_x64(True) if x64 else contextlib.nullcontext()
    with ctx:
        jaxpr = jax.make_jaxpr(fn)(*args)
    s = JaxprSummary()
    total = s.walk(jaxpr.jaxpr)
    s.collectives = total
    return s


def audit_summary(s: JaxprSummary, label: str) -> List[Finding]:
    """The per-program contract findings for one traced entry point."""
    out: List[Finding] = []
    for dt, path in sorted(s.dtypes.items()):
        if not _dtype_ok(dt):
            out.append(Finding(
                rule="jaxpr-dtype", file=label, line=0,
                detail=f"{dt}",
                message=f"{label}: dtype {dt} at {path or '<top>'} is "
                        "outside the hot-path whitelist "
                        "(f32/bf16/i8/i32/u32/bool) — an accidental "
                        "promotion doubles wire and HBM cost"))
    for nm, path in s.callbacks:
        out.append(Finding(
            rule="jaxpr-callback", file=label, line=0,
            detail=f"{nm}@{path}",
            message=f"{label}: host callback primitive {nm} at "
                    f"{path or '<top>'} serializes the round against "
                    "the host — hoist it out of the jitted body"))
    for path, branches in s.cond_mismatches:
        out.append(Finding(
            rule="jaxpr-cond-collective", file=label, line=0,
            detail=f"cond@{path}",
            message=f"{label}: lax.cond at {path} has branch-dependent "
                    f"collectives {branches} — a data-dependent branch "
                    "choice deadlocks multi-host SPMD (all processes "
                    "must issue the identical collective sequence)"))
    return out


# -- central-algorithm audit ------------------------------------------------

def build_central_algo(name: str, agg_impl: str = "bucketed",
                       n_clients: int = 16, use_mesh: bool = True,
                       frac: float = 0.5, donate: bool = True):
    """A tiny audit instance of fedavg/salientgrads with the guard on
    (so the quarantine ``lax.cond`` is in the program) and a collective-
    emitting ``agg_impl``, its training data sharded over the test mesh
    so ``_aggregate`` takes the ``shard_map`` path.

    ``frac < 1`` (C=16, S=8 — S stays divisible by the 8-device mesh
    axis) makes the donation ledger's trained-slice number meaningful:
    at full participation the trained slice IS the whole stack, so a
    donated round would look no smaller than an un-donated one.
    ``donate`` mirrors the CLI's ``--donate_state`` default; the
    ``--jaxpr-no-donate`` seeded violation audits a borrowing
    instance."""
    import jax

    from ..algorithms import FedAvg, SalientGrads
    from ..core.state import HyperParams
    from ..data import make_synthetic_federated
    from ..models import create_model
    from ..parallel import make_mesh, shard_over_clients

    data = make_synthetic_federated(
        n_clients=n_clients, samples_per_client=8, test_per_client=4,
        sample_shape=(8, 8, 8, 1))
    n_dev = len(jax.devices())
    mesh = None
    if use_mesh and n_dev >= 2:
        n_axis = n_dev if n_clients % n_dev == 0 else 2
        mesh = make_mesh(n_axis)
        data = data.replace(
            x_train=shard_over_clients(data.x_train, mesh),
            y_train=shard_over_clients(data.y_train, mesh),
            n_train=shard_over_clients(data.n_train, mesh))
    hp = HyperParams(lr=0.05, lr_decay=0.998, momentum=0.9,
                     local_epochs=1, steps_per_epoch=1, batch_size=8)
    cls = {"fedavg": FedAvg, "salientgrads": SalientGrads}[name]
    algo = cls(create_model("small3dcnn", num_classes=1), data, hp,
               loss_type="bce", frac=frac, seed=0, agg_impl=agg_impl,
               guard=True, donate_state=donate)
    return algo, mesh


def round_args(algo, state=None):
    import jax
    import jax.numpy as jnp

    if state is None:
        state = algo.init_state(jax.random.PRNGKey(0))
    # the seeded (contract-checked) draw — arange at full
    # participation, the np.random.seed(0) subset at frac<1
    sel = jnp.asarray(algo._selected_client_indexes(0))
    d = algo.data
    return (state, sel, jnp.asarray(0.0, jnp.float32),
            d.x_train, d.y_train, d.n_train)


def fused_args(algo, state, block: int = 2):
    """Args for a fused block program. The eval cadence is baked into
    the traced program by ``_get_fused_fn(block, eval_every)``, not
    the argument list — callers pair this with that call."""
    import jax.numpy as jnp
    import numpy as np

    host = [algo._fused_host_inputs(r) for r in range(block)]
    host_stack = tuple(
        jnp.asarray(np.stack([h[i] for h in host]))
        for i in range(len(host[0])))
    round_ids = jnp.arange(block, dtype=jnp.float32)
    d = algo.data
    return (state, host_stack, round_ids, *algo._fused_data_args(),
            d.x_test, d.y_test, d.n_test)


def audit_central_algorithm(
    name: str, agg_impl: str = "bucketed", block: int = 2,
    donate: bool = True,
    donation_pins: Optional[Sequence[str]] = None,
) -> Tuple[List[Finding], Dict[str, Any]]:
    """Full audit of one algorithm: unfused round + fused block traced,
    per-program contracts checked, fused-vs-unfused collective multiset
    equality proven, donation report assembled — and, for the entry
    points named in ``donation_pins``, GATED: a pinned entry point
    found un-donated is a ``jaxpr-donation`` finding."""
    import jax

    algo, mesh = build_central_algo(name, agg_impl=agg_impl,
                                    donate=donate)
    if name == "salientgrads":
        state = algo.init_state(jax.random.PRNGKey(0))
        algo._ensure_agg_plan(state)
    else:
        state = algo.init_state(jax.random.PRNGKey(0))
    rargs = round_args(algo, state)
    unfused = summarize(algo._round_jit, *rargs)
    fused_fn = algo._get_fused_fn(block, 1)
    fargs = fused_args(algo, state, block=block)
    fused = summarize(fused_fn, *fargs)

    label_u = f"jaxpr:{name}:round"
    label_f = f"jaxpr:{name}:fused"
    findings = audit_summary(unfused, label_u) + \
        audit_summary(fused, label_f)
    mu = unfused.collective_multiset()
    mf = fused.collective_multiset()
    if mu != mf:
        findings.append(Finding(
            rule="jaxpr-collective-parity", file=f"jaxpr:{name}",
            line=0, detail="fused-vs-unfused",
            message=f"{name}: collective multiset differs between the "
                    f"fused scan ({mf}) and the unfused round ({mu}) — "
                    "a fused block on a pod would issue a different "
                    "collective sequence than the per-round path it is "
                    "bit-pinned against"))
    donation = donation_audit(algo, state, rargs)
    rows = {r["entry_point"]: r for r in donation}
    for pin in donation_pins or ():
        if not pin.startswith(name + "."):
            continue
        row = rows.get(pin)
        if row is None or not row["donated"]:
            findings.append(Finding(
                rule="jaxpr-donation", file=f"jaxpr:{name}", line=0,
                detail=pin,
                message=f"{pin}: pinned donated in the baseline's "
                        "donated_entry_points but the traced entry "
                        "point does not donate its state — a "
                        "regression to borrow semantics re-allocates "
                        f"{row['state_bytes'] if row else '?'} state "
                        "bytes per call (the Round-13 (1+C)-model "
                        "rewrite the ownership protocol removed)"))
    report = {
        "algorithm": name,
        "agg_impl": agg_impl,
        "on_mesh": mesh is not None,
        "donate_state": bool(algo._donate),
        "collectives_round": mu,
        "collectives_fused": mf,
        "dtypes_round": sorted(unfused.dtypes),
        "dtypes_fused": sorted(fused.dtypes),
        "donation": donation,
    }
    return findings, report


# -- donation audit ---------------------------------------------------------

def _tree_bytes(tree) -> int:
    import jax

    return sum(
        int(getattr(x, "size", 0)) * int(getattr(x, "dtype", None)
                                         and x.dtype.itemsize or 0)
        for x in jax.tree_util.tree_leaves(tree))


def _donated_args(fn, args) -> Optional[List[bool]]:
    """Per-argument donation flags via ``Lowered.args_info`` (trace
    only, no compile). None when this jax version hides them."""
    import jax

    try:
        info = fn.lower(*args).args_info
        return [bool(a.donated)
                for a in jax.tree_util.tree_leaves(
                    info, is_leaf=lambda x: hasattr(x, "donated"))]
    except Exception:
        return None


def trained_slice_bytes(algo, state, s_frac: Optional[float] = None
                        ) -> int:
    """The state bytes a DONATED round still writes fresh per call:
    the new global model plus the trained clients' rows of every
    stacked field (personal stack, topk residual, eval cache) — the
    rest of the state aliases in place. ``s_frac`` defaults to the
    instance's participation fraction; 1.0 for entry points that
    rewrite every row (the finetune pass)."""
    if s_frac is None:
        s_frac = algo.clients_per_round / max(1, algo.num_clients)
    g = _tree_bytes(getattr(state, "global_params", None))
    stacked = 0
    for field in ("personal_params", "agg_residual", "eval_cache"):
        stacked += _tree_bytes(getattr(state, field, None))
    return int(g + s_frac * stacked)


def donation_audit(algo, state, rargs) -> List[Dict[str, Any]]:
    """Rows: every jit entry point, whether any argument is donated,
    and its per-call realloc bytes — the full state for a borrowing
    (un-donated) entry (the [C, model] personal stack dominates —
    RESULTS.md item 6's ~7%-of-round full rewrite), the trained-slice
    bytes (``trained_slice_bytes``) for a donating one (aliasing
    leaves only the freshly-written global + S stacked rows)."""
    import jax

    d = algo.data
    state_bytes = _tree_bytes(state)
    model_bytes = _tree_bytes(state.global_params)
    slice_bytes = trained_slice_bytes(algo, state)
    full_rewrite = trained_slice_bytes(algo, state, s_frac=1.0)
    # (name, fn, args, undonated realloc, donated realloc)
    entries: List[Tuple[str, Any, Tuple, int, int]] = [
        ("_round_jit", algo._round_jit, rargs, state_bytes,
         slice_bytes),
    ]
    if hasattr(algo, "_finetune_jit"):
        entries.append(("_finetune_jit", algo._finetune_jit,
                        (state, d.x_train, d.y_train, d.n_train),
                        state_bytes, full_rewrite))
    if hasattr(algo, "_global_mask_jit"):
        entries.append((
            "_global_mask_jit", algo._global_mask_jit,
            (state.global_params, d.x_train, d.y_train, d.n_train,
             jax.random.PRNGKey(0)),
            # borrow: params re-broadcast + fresh mask; donate: only
            # the mask output is fresh (params alias through)
            _tree_bytes(state.global_params), model_bytes))
    entries.append(("_eval_global", algo._eval_global,
                    (state.global_params, d.x_test, d.y_test, d.n_test),
                    0, 0))  # eval outputs are scalars; nothing to donate
    if state.personal_params is not None:
        entries.append(("_eval_personal", algo._eval_personal,
                        (state.personal_params, d.x_test, d.y_test,
                         d.n_test), 0, 0))
    fused_fn = algo._get_fused_fn(2, 1)
    entries.append(("fused[2,1]", fused_fn,
                    fused_args(algo, state, 2), state_bytes,
                    slice_bytes))
    rows = []
    for name, fn, args, realloc, donated_realloc in entries:
        flags = _donated_args(fn, args)
        donated = any(flags) if flags else False
        rows.append({
            "entry_point": f"{algo.name}.{name}",
            "donated": donated,
            "donation_introspection": flags is not None,
            "state_bytes": realloc,
            "realloc_bytes_per_call": (donated_realloc if donated
                                       else realloc),
        })
    return rows


def audit_algorithms(
    names: Sequence[str] = ("fedavg", "salientgrads"),
    agg_impl: str = "bucketed",
    donate: bool = True,
    donation_pins: Optional[Sequence[str]] = None,
) -> Tuple[List[Finding], Dict[str, Any]]:
    findings: List[Finding] = []
    reports: Dict[str, Any] = {}
    for name in names:
        f, rep = audit_central_algorithm(
            name, agg_impl=agg_impl, donate=donate,
            donation_pins=donation_pins)
        findings.extend(f)
        reports[name] = rep
    # a pin no audited algorithm consumed (typo'd prefix, or an algo
    # dropped from the audit set) would otherwise read as enforced
    # while checking nothing — the same dead-excuse drift the
    # stale-baseline machinery exists to catch for entries[]
    for pin in donation_pins or ():
        if not any(pin.startswith(n + ".") for n in names):
            findings.append(Finding(
                rule="jaxpr-donation", file="jaxpr", line=0,
                detail=pin,
                message=f"donated_entry_points pin {pin!r} matches no "
                        f"audited algorithm ({list(names)}) — it "
                        "enforces nothing; fix the prefix or delete "
                        "the pin"))
    return findings, reports

"""Fusion-rule registry: substitute fused Pallas kernels into LaunchPlans.

A ``FusionRule`` pattern-matches a contiguous eqn window in a ``Trace``
(by primitive-name sequence, then by exact dataflow + shape checks),
and lowers the whole window to ONE fused kernel launch from
``repro.kernels.fused``.  ``fused_plan`` overlays verified matches onto
any base ``LaunchPlan`` — each window becomes a single rule-tagged
segment, and ``PlanExecutor`` dispatches the fused kernel instead of
replaying the member eqns.  This closes the paper's loop: characterize
the decode stream, find the CPU-bound launch-dominated windows, and
replace multi-kernel subgraphs with fused kernels that cut both the
launch count and the intermediate HBM traffic.

Safety: a match is only substituted after a numeric-equivalence check —
the window replay and the fused kernel run on synthetic inputs drawn
from the window's avals and must agree within ``tol``.  Windows whose
intermediates escape (consumed outside the window beyond what the fused
kernel returns) are rejected at bind time, so every fused plan stays an
exact, numerically-equivalent cover of the trace.

The rules match the fp32 window and its bf16 form, where
``convert_element_type`` eqns move the stream to f32 for the statistics
and back.  A bf16 output may differ from the replay by rounding alone,
so its check allows two of its own ulps at the output's magnitude on
top of ``tol``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.extend.core as jexc
import numpy as np

from repro.core.tracing import Trace, _is_drop
from repro.runtime.plan import LaunchPlan

# square .. mul is the 9-eqn RMSNorm core the decode trace emits at every
# block boundary (fp32: the astype round trips are no-ops and elided)
_RMSNORM_CORE = ("square", "reduce_sum", "broadcast_in_dim", "div", "add",
                 "rsqrt", "mul", "broadcast_in_dim", "mul")
# the same core on a low-precision (bf16) stream: x and the weight are
# converted to f32 for the arithmetic and the product is cast back
_RMSNORM_CAST = (("convert_element_type",) + _RMSNORM_CORE[:7]
                 + ("convert_element_type",) + _RMSNORM_CORE[7:]
                 + ("convert_element_type",))

DEFAULT_TOL = 1e-4


def _base(v):
    """Base of a rewritten invar: a jaxpr Var, a Literal, or a const value
    wrapped as ("const", value)."""
    while isinstance(v, tuple):
        if v[0] == "const":
            return v
        v = v[1]
    return v


def _read_ref(env, v):
    """Read a rewritten invar ref against a var->value env."""
    b = _base(v)
    if isinstance(b, jexc.Literal):
        return b.val
    if isinstance(b, tuple):          # ("const", value)
        return b[1]
    return env[b]


def segment_free_outs(flat_eqns, seg):
    """Free inputs and non-drop outputs of one plan segment.

    Free inputs are the vars read before being defined inside the
    segment (consts and literals excluded — they are baked into the
    eqn invars).  Shared with ``PlanExecutor._build``.
    """
    eqns = [flat_eqns[i] for i in seg]
    defined = set()
    free = []
    for eqn, invars in eqns:
        for v in invars:
            b = _base(v)
            if isinstance(b, (tuple, jexc.Literal)):
                continue
            if b not in defined and b not in free:
                free.append(b)
        for ov in eqn.outvars:
            if not _is_drop(ov):
                defined.add(ov)
    outs = [ov for eqn, _ in eqns for ov in eqn.outvars if not _is_drop(ov)]
    return eqns, free, outs


def live_outs(trace: Trace, start: int, stop: int) -> set:
    """Window outvars consumed after the window or returned by the trace."""
    window = {ov for i in range(start, stop)
              for ov in trace.flat_eqns[i][0].outvars if not _is_drop(ov)}
    live = set()
    for j in range(stop, len(trace.flat_eqns)):
        for v in trace.flat_eqns[j][1]:
            b = _base(v)
            if not isinstance(b, (tuple, jexc.Literal)) and b in window:
                live.add(b)
    for ov in trace.closed.jaxpr.outvars:
        if isinstance(ov, jexc.Literal):
            continue
        b = _base(trace.env_map.get(ov, ov))
        if b in window:
            live.add(b)
    return live


@dataclass
class RuleMatch:
    """One verified occurrence of a rule in a trace."""
    rule_name: str
    start: int
    stop: int                          # exclusive eqn index
    inputs: dict                       # role -> rewritten invar ref
    provides: dict                     # outvar -> fused-result index
    eps: float
    max_abs_err: float = float("nan")  # numeric check result (nan = unchecked)
    round_bound: float = 0.0           # rounding allowance of bf16 outputs

    @property
    def indices(self) -> tuple:
        return tuple(range(self.start, self.stop))


def _literal_operand(invars):
    for v in invars:
        if isinstance(v, jexc.Literal):
            return v
    return None


def _var_operands(invars):
    return [v for v in invars if not isinstance(v, jexc.Literal)]


@dataclass(frozen=True)
class RMSNormRule:
    """The RMSNorm window family: plain norm, residual+norm, norm+matmul.

    ``residual`` prepends the block-boundary ``add``; ``matmul`` appends
    the projection ``dot_general``.  All three lower to the fused Pallas
    kernels in ``repro.kernels.fused``; the caller says whether they
    interpret (``repro.kernels.pallas_interpret``).
    """
    name: str
    residual: bool = False
    matmul: bool = False

    def pattern(self, cast: bool = False) -> tuple:
        """Eqn names of the window.  ``cast`` is the low-precision form: a
        bf16 stream is converted to f32 for the statistics, the weight is
        converted before its broadcast, and the product is cast back."""
        pat = _RMSNORM_CAST if cast else _RMSNORM_CORE
        if self.residual:
            pat = ("add",) + pat
        if self.matmul:
            pat = pat + ("dot_general",)
        return pat

    # ------------------------------------------------------------ bind
    def bind(self, trace: Trace, start: int) -> Optional[RuleMatch]:
        flat = trace.flat_eqns
        for cast in (False, True):
            pat = self.pattern(cast)
            stop = start + len(pat)
            if stop <= len(flat) and tuple(
                    flat[i][0].primitive.name
                    for i in range(start, stop)) == pat:
                return self._bind(trace, start, stop, cast)
        return None

    def _bind(self, trace: Trace, start: int, stop: int,
              cast: bool) -> Optional[RuleMatch]:
        eqns = [trace.flat_eqns[i] for i in range(start, stop)]
        body = eqns[(1 if self.residual else 0):
                    len(eqns) - (1 if self.matmul else 0)]
        if cast:
            (cx, sq, rs, bc1, dv, ad, rq, m1, cw, bc2, m2, co) = body
        else:
            (sq, rs, bc1, dv, ad, rq, m1, bc2, m2) = body

        def out(e):
            return e[0].outvars[0]

        x_ref = (cx if cast else sq)[1][0]
        x_b = _base(x_ref)
        if isinstance(x_b, jexc.Literal):
            return None
        # the f32 stream the statistics read (x itself without casts)
        xf = out(cx) if cast else x_b
        if cast:
            x_dtype = cx[0].invars[0].aval.dtype
            if (cx[0].params.get("new_dtype") != np.float32
                    or cw[0].params.get("new_dtype") != np.float32
                    or co[0].params.get("new_dtype") != x_dtype
                    or _base(sq[1][0]) is not xf):
                return None
        x_aval = sq[0].invars[0].aval
        if len(x_aval.shape) < 1:
            return None
        d = x_aval.shape[-1]
        axis = len(x_aval.shape) - 1

        # the norm core must be one connected chain over the last axis
        if rs[0].params.get("axes") != (axis,):
            return None
        if _base(rs[1][0]) is not out(sq):
            return None
        if _base(bc1[1][0]) is not out(rs):
            return None
        # div is non-commutative: the sum must be the dividend and the
        # literal D the divisor (sum/D = mean, never D/sum)
        if _base(dv[1][0]) is not out(bc1):
            return None
        lit_d = dv[1][1] if isinstance(dv[1][1], jexc.Literal) else None
        if lit_d is None or float(lit_d.val) != float(d):
            return None
        lit_eps = _literal_operand(ad[1])
        if lit_eps is None:
            return None
        if not any(_base(v) is out(dv) for v in ad[1]):
            return None
        if _base(rq[1][0]) is not out(ad):
            return None
        m1_bases = [_base(v) for v in _var_operands(m1[1])]
        if out(rq) not in m1_bases or xf not in m1_bases:
            return None
        w_eqn = cw if cast else bc2
        w_ref = w_eqn[1][0]
        if tuple(w_eqn[0].invars[0].aval.shape) != (d,):
            return None
        if cast and _base(bc2[1][0]) is not out(cw):
            return None
        if bc2[0].params.get("broadcast_dimensions") != (axis,):
            return None
        m2_bases = [_base(v) for v in _var_operands(m2[1])]
        if out(m1) not in m2_bases or out(bc2) not in m2_bases:
            return None
        if cast and _base(co[1][0]) is not out(m2):
            return None
        normed = out(co) if cast else out(m2)

        inputs = {"x": x_ref, "weight": w_ref}
        provides = {normed: 0}

        if self.residual:
            add0 = eqns[0]
            if out(add0) is not x_b:
                return None
            a_ref, b_ref = add0[1][0], add0[1][1]
            for v, ref in ((add0[0].invars[0], a_ref),
                           (add0[0].invars[1], b_ref)):
                if isinstance(_base(ref), jexc.Literal):
                    return None
                if tuple(v.aval.shape) != tuple(x_aval.shape):
                    return None
            inputs = {"x": a_ref, "residual": b_ref, "weight": w_ref}
            # fused result order: (normed, pre-norm sum)
            provides = {normed: 0, out(add0): 1}

        if self.matmul:
            dot = eqns[-1]
            dims = dot[0].params.get("dimension_numbers")
            if dims != (((axis,), (0,)), ((), ())):
                return None
            if _base(dot[1][0]) is not normed:
                return None
            p_ref = dot[1][1]
            p_aval = dot[0].invars[1].aval
            if len(p_aval.shape) != 2 or p_aval.shape[0] != d:
                return None
            inputs["w_proj"] = p_ref
            # fused result order: (projection, normed)
            provides = {out(dot): 0, normed: 1}

        # every escaping intermediate must be one the kernel returns
        if not live_outs(trace, start, stop) <= set(provides):
            return None
        return RuleMatch(self.name, start, stop, inputs, provides,
                         eps=float(lit_eps.val))

    # ------------------------------------------------------------ lower
    def lower(self, match: RuleMatch, free: Sequence, *, interpret: bool):
        """Fused callable over the segment's free-var values, plus the
        ordered outvars it defines (``PlanExecutor`` seg_fn contract)."""
        from repro.kernels.fused import residual_rmsnorm, rmsnorm_matmul

        inputs, eps = match.inputs, match.eps
        outs = sorted(match.provides, key=match.provides.get)
        idx = [match.provides[o] for o in outs]
        residual, matmul = self.residual, self.matmul

        def fused_fn(vals, _free=tuple(free)):
            env = dict(zip(_free, vals))
            x = _read_ref(env, inputs["x"])
            w = _read_ref(env, inputs["weight"])
            if matmul:
                res = rmsnorm_matmul(x, w, _read_ref(env, inputs["w_proj"]),
                                     eps=eps, interpret=interpret)
            elif residual:
                res = residual_rmsnorm(x, w,
                                       _read_ref(env, inputs["residual"]),
                                       eps=eps, interpret=interpret)
            else:
                res = residual_rmsnorm(x, w, eps=eps, interpret=interpret)
            return [res[i] for i in idx]

        return fused_fn, outs


# priority order: longest window first, residual before bare norm
REGISTRY = {
    "rmsnorm_matmul": RMSNormRule("rmsnorm_matmul", matmul=True),
    "residual_rmsnorm": RMSNormRule("residual_rmsnorm", residual=True),
    "rmsnorm": RMSNormRule("rmsnorm"),
}
DEFAULT_RULES = tuple(REGISTRY)


def get_rule(name: str):
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown fusion rule {name!r}; "
                       f"registered: {sorted(REGISTRY)}") from None


# per-(rule, window signature) numeric-check cache: binding is structural,
# so one verified signature covers every repetition across layers
_VERIFY_CACHE: dict = {}


def _window_signature(trace: Trace, match: RuleMatch, free) -> tuple:
    avals = tuple((tuple(getattr(v, "aval", None).shape),
                   str(getattr(v, "aval", None).dtype))
                  if hasattr(v, "aval") else ("const",) for v in free)
    return (match.rule_name, match.eps, avals)


def verify_match(trace: Trace, match: RuleMatch) -> float:
    """Numeric equivalence: window replay vs fused kernel on synthetic
    inputs drawn from the free-var avals.  Returns max abs error over the
    provided outputs; cached per window signature."""
    seg = match.indices
    eqns, free, _ = segment_free_outs(trace.flat_eqns, seg)
    key = _window_signature(trace, match, free)
    if key in _VERIFY_CACHE:
        match.max_abs_err, match.round_bound = _VERIFY_CACHE[key]
        return match.max_abs_err

    rng = np.random.default_rng(0)
    vals = []
    for v in free:
        aval = v.aval
        if np.issubdtype(np.dtype(aval.dtype), np.floating):
            sample = rng.standard_normal(aval.shape)
        else:
            sample = np.ones(aval.shape)
        vals.append(jax.numpy.asarray(sample.astype(aval.dtype)))

    env = dict(zip(free, vals))
    for eqn, invars in eqns:
        invals = [_read_ref(env, v) for v in invars]
        out = eqn.primitive.bind(*invals, **eqn.params)
        if not eqn.primitive.multiple_results:
            out = [out]
        for ov, o in zip(eqn.outvars, out):
            if not _is_drop(ov):
                env[ov] = o

    from repro.kernels import pallas_interpret
    rule = get_rule(match.rule_name)
    fused_fn, outs = rule.lower(match, free, interpret=pallas_interpret())
    fused = fused_fn(vals)
    err = bound = 0.0
    for ov, o in zip(outs, fused):
        ref = np.asarray(env[ov], np.float64)
        err = max(err, float(np.max(np.abs(ref - np.asarray(o, np.float64)))))
        if jax.numpy.dtype(o.dtype).itemsize < 4:
            ulp = float(jax.numpy.finfo(o.dtype).eps)
            bound = max(bound, 2 * ulp * float(np.max(np.abs(ref))))
    _VERIFY_CACHE[key] = (err, bound)
    match.max_abs_err, match.round_bound = err, bound
    return err


def find_matches(trace: Trace, rules: Sequence[str] = DEFAULT_RULES, *,
                 verify: bool = True, tol: float = DEFAULT_TOL) -> list:
    """Non-overlapping rule matches, scanned left to right with the
    registry's priority order at each position.  With ``verify`` (the
    default) every match must pass its numeric-equivalence check."""
    names = trace.kernel_names
    matched: list = []
    pos = 0
    while pos < len(names):
        hit = None
        for rn in rules:
            rule = get_rule(rn)
            m = rule.bind(trace, pos)
            if m is None:
                continue
            if verify:
                err = verify_match(trace, m)
                if not (err <= tol + m.round_bound):
                    continue
            hit = m
            break
        if hit is not None:
            matched.append(hit)
            pos = hit.stop
        else:
            pos += 1
    return matched


def fused_plan(trace: Trace, base: Optional[LaunchPlan] = None,
               rules: Sequence[str] = DEFAULT_RULES, *,
               verify: bool = True, tol: float = DEFAULT_TOL,
               matches: Optional[list] = None) -> LaunchPlan:
    """Overlay rule windows onto ``base`` (default: eager).

    Every matched window becomes one rule-tagged segment; base segments
    are split around the windows, so the result remains an exact
    in-order cover and the plan stays numerically equivalent.
    """
    n = len(trace.kernels)
    if base is None:
        base = LaunchPlan.eager(n)
    if matches is None:
        matches = find_matches(trace, rules, verify=verify, tol=tol)
    window_of = {}
    for m in matches:
        for i in m.indices:
            window_of[i] = m
    segments: list = []
    plan_rules: list = []
    cur: list = []
    for seg in base.segments:
        for i in seg:
            m = window_of.get(i)
            if m is None:
                cur.append(i)
                continue
            if cur:
                segments.append(tuple(cur))
                cur = []
            if i == m.start:
                plan_rules.append((len(segments), m.rule_name))
                segments.append(m.indices)
        if cur:
            segments.append(tuple(cur))
            cur = []
    return LaunchPlan("fused", tuple(segments),
                      rules=tuple(plan_rules)).validate(n)

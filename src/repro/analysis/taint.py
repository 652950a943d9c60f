"""Padding-taint abstract interpretation over closed jaxprs.

The ragged-fleet contract (PR 4) pads every bucket's user axis to a
common ``k_pad`` and promises padded lanes never influence active rows.
The test suite checks this for specific grids; this pass proves it for
*all inputs* by abstract interpretation of the lowered program.

Abstract domain
---------------
Each value gets an :class:`AbsVal`:

* ``digits`` — which output axes are user-lane structured.  A
  :class:`Digit` ``(axis, sub_stride, extent)`` survives reshapes that
  merge the user axis with others (e.g. ``(K, slot) -> (K*slot,)``): the
  lane of flat coordinate ``c`` is ``(c // sub_stride) % extent``.
* ``lanes`` — what padded-lane elements hold: :class:`Known` (a concrete
  scalar, evaluated through every primitive), :class:`Same` (elementwise
  equal to another value's elements — how parameter deltas cancel to
  zero in the ``local_steps > 1`` path), or :data:`VARIANT` (arbitrary
  finite values).
* ``const`` — whole-array constant scalar, for concrete folding.
* ``poison`` — violation tags that have influenced this value.

The theorem per reduction site: a cross-user reduction is mask-dominated
iff the abstract padded-lane value is the **identity of its monoid**
(``sum``↔0, ``max``↔-inf, ``and``↔True, ...); a ``dot_general``
contraction over the user axis is safe iff either side's padded lanes
are ``Known(0)``.  Everything else that would let a padded lane reach an
active output (gathers indexing along the user axis, scatters writing
across lanes) is flagged at the site.

Stated assumptions (recorded as INFO findings on every certificate):
padded-lane inputs are finite (``0 * x == 0`` needs ``x`` finite — the
engine's schedules guarantee this) and index-typed padded lanes are
in-bounds (``pad_schedule`` writes index 0).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional

import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.analysis.report import AuditReport, Severity

__all__ = ["LaneLabel", "OutContract", "AbsVal", "Digit", "Known", "Same",
           "VARIANT", "analyze_jaxpr"]


# ---------------------------------------------------------------------------
# abstract domain
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Digit:
    """One user-lane-structured axis of a value.

    ``lane(coord) = (coord // sub_stride) % extent`` — ``sub_stride`` and
    ``extent`` keep lane identity through axis merges; a plain user axis
    is ``Digit(axis, 1, K)``.
    """
    axis: int
    sub_stride: int
    extent: int


@dataclass(frozen=True)
class Known:
    """Padded lanes hold exactly this scalar (tracked concretely)."""
    value: object

    def __repr__(self):
        return f"Known({self.value})"


@dataclass(frozen=True)
class Same:
    """Padded lanes equal the corresponding elements of value ``ref``."""
    ref: object  # a jaxpr Var (identity compared)

    def __hash__(self):
        return hash(id(self.ref))

    def __eq__(self, other):
        return isinstance(other, Same) and self.ref is other.ref


class _Variant:
    def __repr__(self):
        return "VARIANT"


VARIANT = _Variant()


@dataclass(frozen=True)
class AbsVal:
    """Abstract value: lane structure + padded-lane contents + constness."""
    digits: tuple = ()          # tuple[Digit], sorted by axis
    lanes: object = None        # Known | Same | VARIANT; None iff no digits
    const: object = None        # scalar if the whole array is constant
    poison: frozenset = frozenset()

    @property
    def marked(self) -> bool:
        return bool(self.digits)

    def digit_axes(self):
        return {d.axis for d in self.digits}


CLEAN = AbsVal()


def _known_zero(lanes) -> bool:
    return isinstance(lanes, Known) and not np.any(np.asarray(lanes.value))


def _join_lanes(a, b):
    if a == b:
        return a
    return VARIANT


def _join(a: AbsVal, b: AbsVal) -> AbsVal:
    """Least upper bound (used for scan/while carries and cond joins)."""
    poison = a.poison | b.poison
    if not a.marked and not b.marked:
        const = a.const if (a.const is not None and a.const == b.const) \
            else None
        return AbsVal(const=const, poison=poison)
    digits = {}
    for d in a.digits + b.digits:
        prev = digits.get(d.axis)
        if prev is None or prev == d:
            digits[d.axis] = d
        else:  # geometry disagreement: widen to full coverage
            digits[d.axis] = Digit(d.axis, 1, 0)
    la = a.lanes if a.marked else (Known(a.const) if a.const is not None
                                   else VARIANT)
    lb = b.lanes if b.marked else (Known(b.const) if b.const is not None
                                   else VARIANT)
    return AbsVal(digits=tuple(sorted(digits.values(),
                                      key=lambda d: d.axis)),
                  lanes=_join_lanes(la, lb), poison=poison)


# ---------------------------------------------------------------------------
# concrete evaluation of Known lanes through primitives
# ---------------------------------------------------------------------------

_UNARY_NP = {
    "neg": np.negative, "abs": np.abs, "sign": np.sign, "floor": np.floor,
    "ceil": np.ceil, "round": np.rint, "exp": np.exp, "exp2": np.exp2,
    "expm1": np.expm1, "log": np.log, "log1p": np.log1p, "sqrt": np.sqrt,
    "rsqrt": lambda x: 1.0 / np.sqrt(x), "cbrt": np.cbrt, "tanh": np.tanh,
    "sin": np.sin, "cos": np.cos, "tan": np.tan,
    "logistic": lambda x: 1.0 / (1.0 + np.exp(-x)),
    "is_finite": np.isfinite, "not": np.logical_not,
    "erf": lambda x: np.vectorize(__import__("math").erf)(x),
    "square": np.square, "real": np.real, "imag": np.imag,
}

_BINARY_NP = {
    "add": np.add, "add_any": np.add, "sub": np.subtract,
    "mul": np.multiply, "div": np.divide, "pow": np.power,
    "max": np.maximum, "min": np.minimum, "rem": np.fmod,
    "atan2": np.arctan2, "nextafter": np.nextafter,
    "and": np.logical_and, "or": np.logical_or, "xor": np.logical_xor,
    "lt": np.less, "le": np.less_equal, "gt": np.greater,
    "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal,
    "shift_left": np.left_shift, "shift_right_logical": np.right_shift,
    "shift_right_arithmetic": np.right_shift,
}

# monoid identities: reduce primitive -> identity check on scalar c
_REDUCE_IDENTITY = {
    "reduce_sum": lambda c, dt: float(c) == 0.0,
    "reduce_prod": lambda c, dt: float(c) == 1.0,
    "reduce_max": lambda c, dt: (bool(c) is False if dt.kind == "b" else
                                 (np.isneginf(c) if dt.kind == "f" else
                                  c == np.iinfo(dt).min)),
    "reduce_min": lambda c, dt: (bool(c) is True if dt.kind == "b" else
                                 (np.isposinf(c) if dt.kind == "f" else
                                  c == np.iinfo(dt).max)),
    "reduce_and": lambda c, dt: bool(c) is True,
    "reduce_or": lambda c, dt: bool(c) is False,
    "argmax": lambda c, dt: False,   # order-sensitive: never identity
    "argmin": lambda c, dt: False,
}

_REDUCE_FOLD = {
    # padded-lane value after reducing n elements each holding c over a
    # NON-user axis
    "reduce_sum": lambda c, n: c * n,
    "reduce_prod": lambda c, n: c ** n,
    "reduce_max": lambda c, n: c,
    "reduce_min": lambda c, n: c,
    "reduce_and": lambda c, n: c,
    "reduce_or": lambda c, n: c,
}

_IDENTITY_PRIMS = {"stop_gradient", "copy", "reduce_precision",
                   "device_put", "sharding_constraint", "optimization_barrier"}


def _np_scalar(x, dtype=None):
    a = np.asarray(x)
    if dtype is not None:
        a = a.astype(dtype)
    return a[()] if a.ndim == 0 else a


# ---------------------------------------------------------------------------
# labels / contracts (the analysis API surface)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LaneLabel:
    """Input label: ``axis`` is the user axis of this (flattened) input.

    ``lanes`` is what padded lanes hold: a scalar (``Known``) or the
    string ``"variant"`` (arbitrary — e.g. schedule indices, whose
    masking the program must therefore re-establish itself).
    ``axis=None`` marks an unlabeled input.
    """
    axis: Optional[int] = None
    lanes: object = "variant"


NO_LABEL = LaneLabel(axis=None)


@dataclass(frozen=True)
class OutContract:
    """Output contract: padded lanes of ``axis`` must be Known(``value``).

    Used for carry outputs that feed the next chunk (the SBC residual):
    proving the contract at the output IS the inductive step that makes
    the certificate hold across chunked/replanned horizons.
    """
    axis: int
    value: object = 0.0


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------


class _Interp:
    def __init__(self, report: AuditReport, program: str):
        self.report = report
        self.program = program
        self.assumptions = set()
        self.n_eqns = 0
        self.n_certified = 0   # mask-dominated cross-user reductions proven
        self.recording = True  # off during scan/while fixpoint warm-up
        self.alias = {}        # var -> canonical var (element-equal values)

    # -- bookkeeping --------------------------------------------------------

    def _finding(self, check, where, detail):
        if self.recording:
            self.report.add(check, Severity.ERROR,
                            f"{self.program}:{where}", detail)
        return frozenset([f"{check}@{where}"])

    def _assume(self, text):
        self.assumptions.add(text)

    def canon(self, v):
        while v in self.alias:
            v = self.alias[v]
        return v

    # -- env helpers --------------------------------------------------------

    def read(self, env, v) -> AbsVal:
        if isinstance(v, Literal):
            val = np.asarray(v.val)
            return AbsVal(const=_np_scalar(val) if val.ndim == 0 else None)
        return env.get(v, CLEAN)

    def lane_of(self, v, a: AbsVal):
        """This operand's contribution to padded-lane elements."""
        if a.marked:
            return a.lanes
        if a.const is not None:
            return Known(a.const)
        if isinstance(v, Literal):
            return VARIANT  # array literal: arbitrary data at lane coords
        return Same(self.canon(v))

    # -- main walk ----------------------------------------------------------

    def run_jaxpr(self, jaxpr: Jaxpr, in_vals, path: str):
        env = {}
        assert len(jaxpr.invars) == len(in_vals), \
            f"{path}: invar arity {len(jaxpr.invars)} != {len(in_vals)}"
        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = val
        for cv in jaxpr.constvars:
            env[cv] = CLEAN
        for i, eqn in enumerate(jaxpr.eqns):
            self.n_eqns += 1
            outs = self.eval_eqn(eqn, env, f"{path}/{i}:{eqn.primitive.name}")
            for ov, o in zip(eqn.outvars, outs):
                env[ov] = o
        return [self.read(env, v) for v in jaxpr.outvars]

    # -- per-equation dispatch ----------------------------------------------

    def eval_eqn(self, eqn, env, where):
        prim = eqn.primitive.name
        ins = [(v, self.read(env, v)) for v in eqn.invars]
        poison = frozenset().union(*(a.poison for _, a in ins)) \
            if ins else frozenset()
        handler = getattr(self, "_p_" + prim.replace("-", "_"), None)
        if handler is not None:
            outs = handler(eqn, ins, where)
        elif prim in _IDENTITY_PRIMS:
            outs = [self._identity(eqn, ins)]
        elif prim in _UNARY_NP:
            outs = [self._elementwise(eqn, ins, _UNARY_NP[prim])]
        elif prim in _BINARY_NP:
            outs = [self._elementwise(eqn, ins, _BINARY_NP[prim])]
        elif prim in _REDUCE_IDENTITY:
            outs = self._reduce(eqn, ins, where)
        else:
            outs = self._unknown(eqn, ins, where)
        return [replace(o, poison=o.poison | poison) for o in outs]

    # elementwise family -----------------------------------------------------

    def _merge_digits(self, eqn, ins):
        """Union the operands' digits onto the (rank-aligned) output."""
        out_shape = eqn.outvars[0].aval.shape
        digits = {}
        agree = True
        for v, a in ins:
            rank = len(getattr(v.aval, "shape", ())) \
                if not isinstance(v, Literal) else np.asarray(v.val).ndim
            for d in a.digits:
                # lax elementwise ops are rank-aligned; scalar operands
                # broadcast and carry no digits
                ax = d.axis + (len(out_shape) - rank)
                nd = Digit(ax, d.sub_stride, d.extent)
                prev = digits.get(ax)
                if prev is None:
                    digits[ax] = nd
                elif prev != nd:
                    digits[ax] = Digit(ax, 1, out_shape[ax])
                    agree = False
        return (tuple(sorted(digits.values(), key=lambda d: d.axis)), agree)

    def _elementwise(self, eqn, ins, np_fn):
        prim = eqn.primitive.name
        out_aval = eqn.outvars[0].aval
        digits, agree = self._merge_digits(eqn, ins)
        consts = [a.const for _, a in ins]
        const = None
        if all(c is not None for c in consts) and not digits:
            with np.errstate(all="ignore"):
                const = _np_scalar(np_fn(*consts), out_aval.dtype)
        if not digits:
            return AbsVal(const=const)
        lanes = [self.lane_of(v, a) for v, a in ins]
        out_lanes = self._combine_lanes(prim, lanes, np_fn, out_aval.dtype) \
            if agree else VARIANT
        return AbsVal(digits=digits, lanes=out_lanes)

    def _combine_lanes(self, prim, lanes, np_fn, dtype):
        if all(isinstance(x, Known) for x in lanes):
            with np.errstate(all="ignore"):
                return Known(_np_scalar(np_fn(*(x.value for x in lanes)),
                                        dtype))
        if prim == "mul" and any(_known_zero(x) for x in lanes):
            self._assume("padded-lane operands are finite (0 * x == 0)")
            return Known(_np_scalar(0, dtype))
        if prim in ("and",) and any(isinstance(x, Known) and not x.value
                                    for x in lanes):
            return Known(False)
        if prim in ("or",) and any(isinstance(x, Known) and bool(x.value)
                                   for x in lanes):
            return Known(True)
        if prim == "div" and _known_zero(lanes[0]):
            self._assume("padded-lane denominators are nonzero "
                         "(0 / d == 0)")
            return Known(_np_scalar(0, dtype))
        if prim == "sub" and isinstance(lanes[0], Same) \
                and lanes[0] == lanes[1]:
            return Known(_np_scalar(0, dtype))
        if prim in ("add", "add_any", "sub") and isinstance(lanes[0], Same) \
                and _known_zero(lanes[1]):
            return lanes[0]
        if prim in ("add", "add_any") and isinstance(lanes[1], Same) \
                and _known_zero(lanes[0]):
            return lanes[1]
        return VARIANT

    def _identity(self, eqn, ins):
        v, a = ins[0]
        if not isinstance(v, Literal):
            self.alias[eqn.outvars[0]] = self.canon(v)
        return a

    # reductions -------------------------------------------------------------

    def _reduce(self, eqn, ins, where):
        prim = eqn.primitive.name
        (v, a), = ins
        axes = eqn.params["axes"]
        in_aval = v.aval
        out_aval = eqn.outvars[0].aval
        hit = [d for d in a.digits if d.axis in axes]
        remaining = [d for d in a.digits if d.axis not in axes]
        # renumber the surviving axes
        new_digits = tuple(
            Digit(d.axis - sum(1 for ax in axes if ax < d.axis),
                  d.sub_stride, d.extent) for d in remaining)
        poison = frozenset()
        if hit:
            ident = _REDUCE_IDENTITY.get(prim)
            ok = (isinstance(a.lanes, Known) and ident is not None
                  and ident(a.lanes.value, np.dtype(in_aval.dtype)))
            if ok:
                self.n_certified += 1
                lanes = a.lanes if prim != "reduce_sum" \
                    else Known(_np_scalar(0, out_aval.dtype))
            else:
                poison = self._finding(
                    "taint.unmasked-reduction", where,
                    f"{prim} over user axis/axes "
                    f"{[d.axis for d in hit]} with padded lanes {a.lanes} "
                    "— not the monoid identity, padded users leak into "
                    "active outputs")
                lanes = VARIANT
        else:
            n = int(np.prod([in_aval.shape[ax] for ax in axes], dtype=int)) \
                if axes else 1
            if isinstance(a.lanes, Known) and prim in _REDUCE_FOLD:
                with np.errstate(all="ignore"):
                    lanes = Known(_np_scalar(
                        _REDUCE_FOLD[prim](a.lanes.value, n),
                        out_aval.dtype))
            elif prim in ("argmax", "argmin"):
                lanes = VARIANT
            else:
                lanes = VARIANT if not isinstance(a.lanes, Known) else VARIANT
        if not new_digits:
            if hit and not poison:
                # fully reduced, certified: result carries no lane structure
                return [AbsVal(poison=poison)]
            return [AbsVal(poison=poison)] if hit else [
                AbsVal(const=None, poison=poison)]
        return [AbsVal(digits=new_digits, lanes=lanes, poison=poison)]

    def _p_argmax(self, eqn, ins, where):
        return self._reduce(eqn, ins, where)

    def _p_argmin(self, eqn, ins, where):
        return self._reduce(eqn, ins, where)

    def _p_cumsum(self, eqn, ins, where):
        return self._cumulative(eqn, ins, where)

    _p_cumprod = _p_cummax = _p_cummin = _p_cumlogsumexp = _p_cumsum

    def _cumulative(self, eqn, ins, where):
        (v, a), = ins
        axis = eqn.params.get("axis")
        if any(d.axis == axis for d in a.digits):
            poison = self._finding(
                "taint.cumulative-over-user-axis", where,
                f"{eqn.primitive.name} along user axis {axis}: prefix "
                "results mix padded and active lanes")
            return [AbsVal(digits=a.digits, lanes=VARIANT, poison=poison)]
        lanes = a.lanes if isinstance(a.lanes, Known) and \
            eqn.primitive.name in ("cummax", "cummin") else (
                a.lanes if _known_zero(a.lanes)
                and eqn.primitive.name == "cumsum" else
                (VARIANT if a.marked else None))
        return [AbsVal(digits=a.digits, lanes=lanes)]

    # select ----------------------------------------------------------------

    def _p_select_n(self, eqn, ins, where):
        out_aval = eqn.outvars[0].aval
        digits, agree = self._merge_digits(eqn, ins)
        if not digits:
            consts = [a.const for _, a in ins]
            if all(c is not None for c in consts):
                which = int(np.asarray(consts[0]).item())
                return [AbsVal(const=consts[1 + which])]
            return [AbsVal()]
        pred_lane = self.lane_of(*ins[0])
        case_lanes = [self.lane_of(v, a) for v, a in ins[1:]]
        if isinstance(pred_lane, Known):
            lanes = case_lanes[int(np.asarray(pred_lane.value).item())]
        else:
            lanes = case_lanes[0]
            for cl in case_lanes[1:]:
                lanes = _join_lanes(lanes, cl)
        return [AbsVal(digits=digits, lanes=lanes if agree else VARIANT)]

    def _p_clamp(self, eqn, ins, where):
        def np_clamp(lo, x, hi):
            return np.minimum(np.maximum(x, lo), hi)
        return [self._elementwise(eqn, ins, np_clamp)]

    def _p_integer_pow(self, eqn, ins, where):
        y = eqn.params["y"]
        return [self._elementwise(
            eqn, ins, lambda x: np.power(x, y))]

    def _p_convert_element_type(self, eqn, ins, where):
        (v, a), = ins
        out_dtype = np.dtype(eqn.outvars[0].aval.dtype)
        in_kind = np.dtype(v.aval.dtype).kind if not isinstance(v, Literal) \
            else np.asarray(v.val).dtype.kind
        const = _np_scalar(a.const, out_dtype) if a.const is not None \
            else None
        lanes = a.lanes
        if isinstance(lanes, Known):
            lanes = Known(_np_scalar(lanes.value, out_dtype))
        elif isinstance(lanes, Same) and in_kind != out_dtype.kind:
            lanes = VARIANT
        if in_kind == out_dtype.kind and not isinstance(v, Literal):
            self.alias[eqn.outvars[0]] = self.canon(v)
        return [AbsVal(digits=a.digits, lanes=lanes, const=const)]

    # shape ops -------------------------------------------------------------

    def _p_broadcast_in_dim(self, eqn, ins, where):
        (v, a), = ins
        bdims = eqn.params["broadcast_dimensions"]
        out_shape = eqn.params["shape"]
        digits = tuple(Digit(bdims[d.axis], d.sub_stride, d.extent)
                       for d in a.digits)
        if not isinstance(v, Literal) and a.const is None:
            # broadcasting preserves element correspondence along kept axes
            self.alias[eqn.outvars[0]] = self.canon(v)
        const = a.const
        if isinstance(v, Literal) and np.asarray(v.val).ndim == 0:
            const = _np_scalar(v.val)
        return [AbsVal(digits=digits, lanes=a.lanes, const=const)]

    def _p_reshape(self, eqn, ins, where):
        (v, a), = ins
        in_shape = v.aval.shape
        out_shape = eqn.outvars[0].aval.shape
        if eqn.params.get("dimensions") is not None:
            return self._unknown(eqn, ins, where)  # fused transpose: rare
        if not a.marked:
            return [AbsVal(const=a.const)]
        in_strides = _row_major_strides(in_shape)
        out_strides = _row_major_strides(out_shape)
        digits = []
        degraded = False
        for d in a.digits:
            g = in_strides[d.axis] * d.sub_stride  # global flat stride
            placed = False
            for j, (so, sz) in enumerate(zip(out_strides, out_shape)):
                if (g % so == 0 and so <= g and g * d.extent <= so * sz):
                    digits.append(Digit(j, g // so, d.extent))
                    placed = True
                    break
            if not placed:
                # lane structure split across axes: widen every axis the
                # digit's span overlaps
                degraded = True
                span_lo, span_hi = g, g * d.extent
                for j, (so, sz) in enumerate(zip(out_strides, out_shape)):
                    if so < span_hi and so * sz > span_lo // max(1, sz):
                        digits.append(Digit(j, 1, sz))
        dd = {}
        for d in digits:
            dd[d.axis] = d if d.axis not in dd else Digit(
                d.axis, 1, out_shape[d.axis])
        return [AbsVal(digits=tuple(sorted(dd.values(),
                                           key=lambda d: d.axis)),
                       lanes=a.lanes if not degraded else VARIANT)]

    def _p_transpose(self, eqn, ins, where):
        (v, a), = ins
        perm = eqn.params["permutation"]
        inv = {old: new for new, old in enumerate(perm)}
        digits = tuple(sorted(
            (Digit(inv[d.axis], d.sub_stride, d.extent) for d in a.digits),
            key=lambda d: d.axis))
        return [AbsVal(digits=digits, lanes=a.lanes, const=a.const)]

    def _p_squeeze(self, eqn, ins, where):
        (v, a), = ins
        dims = eqn.params["dimensions"]
        digits = tuple(
            Digit(d.axis - sum(1 for ax in dims if ax < d.axis),
                  d.sub_stride, d.extent)
            for d in a.digits if d.axis not in dims)
        return [AbsVal(digits=digits,
                       lanes=a.lanes if digits else None, const=a.const)]

    def _p_expand_dims(self, eqn, ins, where):
        (v, a), = ins
        dims = eqn.params["dimensions"]
        digits = tuple(
            Digit(d.axis + sum(1 for ax in dims if ax <= d.axis),
                  d.sub_stride, d.extent) for d in a.digits)
        return [AbsVal(digits=digits, lanes=a.lanes, const=a.const)]

    def _p_rev(self, eqn, ins, where):
        (v, a), = ins
        # reversal permutes lanes but keeps the axis lane-partitioned
        return [AbsVal(digits=a.digits,
                       lanes=a.lanes if isinstance(a.lanes, Known)
                       else (VARIANT if a.marked else None),
                       const=a.const)]

    def _p_pad(self, eqn, ins, where):
        (v, a), (pv, pa) = ins
        cfg = eqn.params["padding_config"]
        out_shape = eqn.outvars[0].aval.shape
        pad_lane = Known(pa.const) if pa.const is not None \
            else self.lane_of(pv, pa)
        digits = []
        lanes = a.lanes
        for d in a.digits:
            lo, hi, interior = cfg[d.axis]
            if lo == 0 and hi == 0 and interior == 0:
                digits.append(d)
            else:
                digits.append(Digit(d.axis, 1, out_shape[d.axis]))
                lanes = _join_lanes(lanes, pad_lane) if lanes is not None \
                    else pad_lane
        const = a.const if (a.const is not None and pa.const is not None
                            and a.const == pa.const) else None
        return [AbsVal(digits=tuple(digits), lanes=lanes, const=const)]

    def _p_slice(self, eqn, ins, where):
        (v, a), = ins
        start = eqn.params["start_indices"]
        limit = eqn.params["limit_indices"]
        strides = eqn.params["strides"] or (1,) * len(start)
        in_shape = v.aval.shape
        out_shape = eqn.outvars[0].aval.shape
        digits = []
        lanes = a.lanes
        for d in a.digits:
            ax = d.axis
            if start[ax] == 0 and limit[ax] == in_shape[ax] \
                    and strides[ax] == 1:
                digits.append(d)
            else:
                digits.append(Digit(ax, 1, out_shape[ax]))
                if not isinstance(lanes, Known):
                    lanes = VARIANT
        return [AbsVal(digits=tuple(digits),
                       lanes=lanes if digits else None, const=a.const)]

    def _p_split(self, eqn, ins, where):
        # jnp.split: consecutive slices along one axis, one per output
        (v, a), = ins
        axis = eqn.params["axis"]
        outs = []
        for ov in eqn.outvars:
            extent = ov.aval.shape[axis]
            digits = []
            lanes = a.lanes
            for d in a.digits:
                if d.axis != axis or extent == v.aval.shape[axis]:
                    digits.append(d)
                else:
                    digits.append(Digit(axis, 1, extent))
                    if not isinstance(lanes, Known):
                        lanes = VARIANT
            outs.append(AbsVal(digits=tuple(digits),
                               lanes=lanes if digits else None,
                               const=a.const))
        return outs

    def _p_concatenate(self, eqn, ins, where):
        dim = eqn.params["dimension"]
        out_shape = eqn.outvars[0].aval.shape
        digits, agree = self._merge_digits(eqn, ins)
        if not digits:
            return [AbsVal()]
        on_dim = any(d.axis == dim for d in digits)
        lanes = None
        for v, a in ins:
            contrib = self.lane_of(v, a)
            lanes = contrib if lanes is None else _join_lanes(lanes, contrib)
        if on_dim:
            digits = tuple(d if d.axis != dim else Digit(dim, 1,
                                                         out_shape[dim])
                           for d in digits)
        return [AbsVal(digits=digits,
                       lanes=lanes if agree else VARIANT)]

    def _p_iota(self, eqn, ins, where):
        return [AbsVal()]

    def _p_dynamic_slice(self, eqn, ins, where):
        (v, a) = ins[0]
        out_shape = eqn.outvars[0].aval.shape
        in_shape = v.aval.shape
        digits = []
        lanes = a.lanes
        for d in a.digits:
            if out_shape[d.axis] == in_shape[d.axis]:
                digits.append(d)
            else:
                digits.append(Digit(d.axis, 1, out_shape[d.axis]))
                if not isinstance(lanes, Known):
                    lanes = VARIANT
        return [AbsVal(digits=tuple(digits),
                       lanes=lanes if digits else None)]

    def _p_dynamic_update_slice(self, eqn, ins, where):
        digits, agree = self._merge_digits(eqn, ins[:2])
        if not digits:
            return [AbsVal()]
        lo = self.lane_of(*ins[0])
        lu = self.lane_of(*ins[1])
        return [AbsVal(digits=digits,
                       lanes=_join_lanes(lo, lu) if agree else VARIANT)]

    # contraction / indexing -------------------------------------------------

    def _p_dot_general(self, eqn, ins, where):
        (lv, la), (rv, ra) = ins[:2]
        ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
        l_rank = len(lv.aval.shape)
        r_rank = len(rv.aval.shape)
        l_free = [ax for ax in range(l_rank) if ax not in lc and ax not in lb]
        r_free = [ax for ax in range(r_rank) if ax not in rc and ax not in rb]
        l_lane = self.lane_of(lv, la)
        r_lane = self.lane_of(rv, ra)
        poison = frozenset()
        # contracted user axes: the cross-user reduction case
        contracted_hit = [d for d in la.digits if d.axis in lc] + \
                         [d for d in ra.digits if d.axis in rc]
        if contracted_hit:
            if _known_zero(l_lane) or _known_zero(r_lane):
                self.n_certified += 1
                self._assume("padded-lane operands are finite (0 * x == 0)")
            else:
                poison = self._finding(
                    "taint.unmasked-contraction", where,
                    f"dot_general contracts user axis with padded lanes "
                    f"lhs={l_lane} rhs={r_lane} — neither side is "
                    "Known(0), padded users leak into the product")
        # batch/free user axes survive into the output
        out_digits = []

        def out_pos_l(ax):
            if ax in lb:
                return lb.index(ax)
            return len(lb) + l_free.index(ax)

        def out_pos_r(ax):
            if ax in rb:
                return rb.index(ax)
            return len(lb) + len(l_free) + r_free.index(ax)

        for d in la.digits:
            if d.axis in lc:
                continue
            out_digits.append(Digit(out_pos_l(d.axis), d.sub_stride,
                                    d.extent))
        for d in ra.digits:
            if d.axis in rc:
                continue
            pos = out_pos_r(d.axis)
            if not any(x.axis == pos for x in out_digits):
                out_digits.append(Digit(pos, d.sub_stride, d.extent))
        out_digits = tuple(sorted(out_digits, key=lambda d: d.axis))
        if not out_digits:
            return [AbsVal(poison=poison)]
        lanes = Known(_np_scalar(0, eqn.outvars[0].aval.dtype)) \
            if (_known_zero(l_lane) or _known_zero(r_lane)) else VARIANT
        if _known_zero(l_lane) or _known_zero(r_lane):
            self._assume("padded-lane operands are finite (0 * x == 0)")
        return [AbsVal(digits=out_digits, lanes=lanes, poison=poison)]

    def _p_gather(self, eqn, ins, where):
        (ov, oa), (iv, ia) = ins
        dn = eqn.params["dimension_numbers"]
        slice_sizes = eqn.params["slice_sizes"]
        op_shape = ov.aval.shape
        out_shape = eqn.outvars[0].aval.shape
        out_rank = len(out_shape)
        idx_rank = len(iv.aval.shape)
        batch_out = [d for d in range(out_rank) if d not in dn.offset_dims]
        ob = tuple(getattr(dn, "operand_batching_dims", ()))
        ib = tuple(getattr(dn, "start_indices_batching_dims", ()))
        digits = []
        lanes = None
        poison = frozenset()

        def add_lane(contrib):
            nonlocal lanes
            lanes = contrib if lanes is None else _join_lanes(lanes, contrib)

        # indices-side digits -> output batch positions
        for d in ia.digits:
            if d.axis >= idx_rank - 1:
                continue  # the index-vector dim is never lane data
            out_ax = batch_out[d.axis] if d.axis < len(batch_out) else None
            if out_ax is None:
                continue
            digits.append(Digit(out_ax, d.sub_stride, d.extent))
            if d.axis in ib:
                pair = ob[ib.index(d.axis)]
                op_digit = next((x for x in oa.digits if x.axis == pair),
                                None)
                if op_digit is not None and isinstance(oa.lanes, Known):
                    # within-lane gather of a uniform lane: still uniform
                    self._assume("index-typed padded lanes are in-bounds "
                                 "(pad_schedule writes index 0)")
                    add_lane(oa.lanes)
                else:
                    add_lane(VARIANT)
            else:
                add_lane(VARIANT)
        # operand-side digits
        op_offset_src = [ax for ax in range(len(op_shape))
                         if ax not in dn.collapsed_slice_dims
                         and ax not in ob]
        for d in oa.digits:
            if d.axis in ob:
                pair_idx_dim = ib[ob.index(d.axis)]
                out_ax = batch_out[pair_idx_dim] \
                    if pair_idx_dim < len(batch_out) else None
                if out_ax is not None \
                        and not any(x.axis == out_ax for x in digits):
                    digits.append(Digit(out_ax, 1, out_shape[out_ax]))
                    add_lane(oa.lanes if isinstance(oa.lanes, Known)
                             else VARIANT)
            elif d.axis in dn.collapsed_slice_dims \
                    or (d.axis in dn.start_index_map
                        and slice_sizes[d.axis] != op_shape[d.axis]):
                poison |= self._finding(
                    "taint.gather-over-user-axis", where,
                    f"gather indexes along user axis {d.axis}: padded-lane "
                    "data can surface at arbitrary output positions")
            elif d.axis in dn.start_index_map:
                # dynamic-slice-style gather whose slice spans the whole
                # user axis: the only in-bounds start is 0 (and gather
                # clamps), so the axis passes through untouched — lane
                # structure is preserved exactly like a full offset dim
                # (e.g. ``x[:, :, -1, :]`` batched over leading user axes)
                self._assume("full-length gather slices start at 0 "
                             "(out-of-range starts clamp to 0)")
                j = op_offset_src.index(d.axis)
                out_ax = dn.offset_dims[j]
                digits.append(Digit(out_ax, d.sub_stride, d.extent))
                add_lane(oa.lanes if isinstance(oa.lanes, Known)
                         else VARIANT)
            else:
                j = op_offset_src.index(d.axis)
                out_ax = dn.offset_dims[j]
                if slice_sizes[d.axis] == op_shape[d.axis]:
                    digits.append(Digit(out_ax, d.sub_stride, d.extent))
                else:
                    digits.append(Digit(out_ax, 1, out_shape[out_ax]))
                add_lane(oa.lanes if isinstance(oa.lanes, Known)
                         else VARIANT)
        digits = tuple(sorted(digits, key=lambda d: d.axis))
        if not digits:
            return [AbsVal(poison=poison)]
        return [AbsVal(digits=digits,
                       lanes=lanes if lanes is not None else VARIANT,
                       poison=poison)]

    def _p_scatter_add(self, eqn, ins, where):
        (ov, oa), (iv, ia), (uv, ua) = ins
        dn = eqn.params["dimension_numbers"]
        op_shape = ov.aval.shape
        upd_shape = uv.aval.shape
        idx_rank = len(iv.aval.shape)
        ob = tuple(getattr(dn, "operand_batching_dims", ()))
        ib = tuple(getattr(dn, "scatter_indices_batching_dims", ()))
        uw = tuple(dn.update_window_dims)
        # updates dims that are NOT window dims map in order to scatter
        # indices dims (excluding the trailing index-vector dim)
        upd_batch = [ax for ax in range(len(upd_shape)) if ax not in uw]
        op_window = [ax for ax in range(len(op_shape))
                     if ax not in dn.inserted_window_dims and ax not in ob]
        u_lane = self.lane_of(uv, ua)
        o_lane = self.lane_of(ov, oa)
        poison = frozenset()
        cross_lane_zero = True
        for d in ua.digits:
            if d.axis in uw:
                continue  # window dims: within-slice, handled via operand
            j = upd_batch.index(d.axis)
            idx_dim = j  # indices dim order
            if idx_dim in ib:
                continue  # batched (within-lane) scatter: confined
            # lane-structured updates scattered across lanes by index value
            if not _known_zero(u_lane):
                cross_lane_zero = False
                poison |= self._finding(
                    "taint.scatter-across-user-axis", where,
                    f"scatter-add writes user-lane updates (lanes={u_lane}) "
                    "at index-selected positions: padded-lane data can "
                    "land in active rows")
        # output keeps the operand's layout
        digits = dict((d.axis, d) for d in oa.digits)
        for d in ua.digits:
            if d.axis in uw:
                op_ax = op_window[uw.index(d.axis)]
                nd = Digit(op_ax, d.sub_stride, d.extent)
                if op_ax not in digits:
                    digits[op_ax] = nd
            else:
                j = upd_batch.index(d.axis)
                if j in ib:
                    op_ax = ob[ib.index(j)]
                    if op_ax not in digits:
                        digits[op_ax] = Digit(op_ax, 1, op_shape[op_ax])
        digits = tuple(sorted(digits.values(), key=lambda d: d.axis))
        if not digits:
            return [AbsVal(poison=poison)]
        if _known_zero(u_lane):
            lanes = o_lane  # adding exact zeros changes nothing
        elif isinstance(o_lane, Known) and isinstance(u_lane, Known):
            lanes = VARIANT  # added at some positions within the lane only
        else:
            lanes = VARIANT
        return [AbsVal(digits=digits, lanes=lanes, poison=poison)]

    _p_scatter = _p_scatter_add  # conservative: same confinement rules

    def _p_sort(self, eqn, ins, where):
        dim = eqn.params["dimension"]
        outs = []
        poison = frozenset()
        for v, a in ins:
            if any(d.axis == dim for d in a.digits):
                poison |= self._finding(
                    "taint.sort-over-user-axis", where,
                    f"sort along user axis {dim} interleaves padded and "
                    "active lanes")
            outs.append(AbsVal(digits=a.digits,
                               lanes=VARIANT if a.marked else None,
                               poison=poison))
        return outs

    def _p_top_k(self, eqn, ins, where):
        (v, a), = ins
        last = len(v.aval.shape) - 1
        poison = frozenset()
        if any(d.axis == last for d in a.digits):
            poison = self._finding(
                "taint.topk-over-user-axis", where,
                "top_k along user axis selects across padded lanes")
        digits = tuple(d for d in a.digits if d.axis != last)
        vals = AbsVal(digits=digits,
                      lanes=a.lanes if isinstance(a.lanes, Known) and digits
                      else (VARIANT if digits else None), poison=poison)
        idxs = AbsVal(digits=digits, lanes=VARIANT if digits else None,
                      poison=poison)
        return [vals, idxs]

    # higher-order -----------------------------------------------------------

    def _p_jit(self, eqn, ins, where):
        closed = eqn.params["jaxpr"]
        return self.run_jaxpr(closed.jaxpr, [a for _, a in ins],
                              where + "/jit")

    def _p_closed_call(self, eqn, ins, where):
        closed = eqn.params["call_jaxpr"]
        return self.run_jaxpr(closed.jaxpr, [a for _, a in ins],
                              where + "/call")

    def _p_custom_jvp_call(self, eqn, ins, where):
        closed = eqn.params["call_jaxpr"]
        return self.run_jaxpr(closed.jaxpr, [a for _, a in ins],
                              where + "/jvp")

    def _p_custom_vjp_call(self, eqn, ins, where):
        closed = eqn.params.get("call_jaxpr") or eqn.params.get("fun_jaxpr")
        return self.run_jaxpr(closed.jaxpr, [a for _, a in ins],
                              where + "/vjp")

    _p_custom_vjp_call_jaxpr = _p_custom_vjp_call

    def _p_remat(self, eqn, ins, where):
        inner = eqn.params["jaxpr"]
        jaxpr = inner.jaxpr if isinstance(inner, ClosedJaxpr) else inner
        return self.run_jaxpr(jaxpr, [a for _, a in ins], where + "/remat")

    _p_remat2 = _p_checkpoint = _p_remat

    def _p_cond(self, eqn, ins, where):
        branches = eqn.params["branches"]
        op_vals = [a for _, a in ins[1:]]
        outs = None
        for bi, br in enumerate(branches):
            bouts = self.run_jaxpr(br.jaxpr, op_vals,
                                   f"{where}/branch{bi}")
            outs = bouts if outs is None else [
                _join(x, y) for x, y in zip(outs, bouts)]
        return outs

    def _p_while(self, eqn, ins, where):
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond_consts = [a for _, a in ins[:cn]]
        body_consts = [a for _, a in ins[cn:cn + bn]]
        carry = [a for _, a in ins[cn + bn:]]
        carry = self._fixpoint(
            lambda c, rec: self._run_quiet(
                eqn.params["body_jaxpr"].jaxpr, body_consts + c,
                f"{where}/body", rec),
            carry, where)
        self._run_quiet(eqn.params["cond_jaxpr"].jaxpr,
                        cond_consts + carry, f"{where}/cond", True)
        return carry

    def _p_scan(self, eqn, ins, where):
        num_consts = eqn.params["num_consts"]
        num_carry = eqn.params["num_carry"]
        body = eqn.params["jaxpr"].jaxpr
        consts = [a for _, a in ins[:num_consts]]
        carry0 = [a for _, a in ins[num_consts:num_consts + num_carry]]
        xs = [(v, a) for v, a in ins[num_consts + num_carry:]]
        # xs lose their leading scan axis entering the body
        xs_body = []
        poison = frozenset()
        for v, a in xs:
            if any(d.axis == 0 for d in a.digits):
                poison |= self._finding(
                    "taint.scan-over-user-axis", where,
                    "lax.scan consumes the user axis as its scan axis")
            digits = tuple(Digit(d.axis - 1, d.sub_stride, d.extent)
                           for d in a.digits if d.axis > 0)
            xs_body.append(AbsVal(digits=digits,
                                  lanes=a.lanes if digits else None,
                                  const=a.const, poison=a.poison))

        def step(c, rec):
            outs = self._run_quiet(body, consts + c + xs_body,
                                   f"{where}/body", rec)
            return outs[:num_carry], outs[num_carry:]

        carry = self._fixpoint(lambda c, rec: step(c, rec)[0], carry0, where)
        _, ys = step(carry, True)
        # ys gain a leading period axis
        ys_out = [AbsVal(digits=tuple(Digit(d.axis + 1, d.sub_stride,
                                            d.extent) for d in y.digits),
                         lanes=y.lanes, const=y.const,
                         poison=y.poison | poison) for y in ys]
        carry_out = [replace(c, poison=c.poison | poison) for c in carry]
        return carry_out + ys_out

    def _run_quiet(self, jaxpr, vals, path, record):
        prev, self.recording = self.recording, record and self.recording
        prev_n = (self.n_eqns, self.n_certified)
        try:
            outs = self.run_jaxpr(jaxpr, vals, path)
        finally:
            self.recording = prev
            if not record:
                self.n_eqns, self.n_certified = prev_n
        return outs

    def _fixpoint(self, step, carry, where, max_iter=24):
        for _ in range(max_iter):
            nxt = [_join(c, n) for c, n in zip(carry, step(carry, False))]
            if nxt == carry:
                return carry
            carry = nxt
        # no convergence: widen everything
        return [AbsVal(digits=c.digits, lanes=VARIANT if c.marked else None,
                       poison=c.poison) for c in carry]

    # fallback ---------------------------------------------------------------

    def _unknown(self, eqn, ins, where):
        marked = any(a.marked for _, a in ins)
        poison = frozenset()
        if marked:
            poison = self._finding(
                "taint.unhandled-primitive", where,
                f"primitive '{eqn.primitive.name}' has no transfer rule "
                "but consumes a user-lane-structured value")
        outs = []
        for ov in eqn.outvars:
            shape = getattr(ov.aval, "shape", ())
            if marked:
                digits = tuple(Digit(ax, 1, s)
                               for ax, s in enumerate(shape) if s > 1)
                outs.append(AbsVal(digits=digits,
                                   lanes=VARIANT if digits else None,
                                   poison=poison))
            else:
                outs.append(AbsVal())
        return outs


def _row_major_strides(shape):
    strides = [1] * len(shape)
    for i in range(len(shape) - 2, -1, -1):
        strides[i] = strides[i + 1] * shape[i + 1]
    return strides


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------


def analyze_jaxpr(closed: ClosedJaxpr, in_labels, out_contracts=None, *,
                  program: str = "program",
                  report: Optional[AuditReport] = None) -> AuditReport:
    """Run the padding-taint pass over one closed jaxpr.

    ``in_labels``: one :class:`LaneLabel` (or :data:`NO_LABEL`) per
    flattened jaxpr input.  ``out_contracts``: optional dict mapping
    flattened output index → :class:`OutContract` (padded lanes of that
    output must provably hold the contract value — the chunk-resumption
    induction).  Findings land in ``report`` (new one if None) and a
    per-program summary in ``report.programs[program]``.
    """
    if report is None:
        report = AuditReport()
    interp = _Interp(report, program)
    in_vals = []
    for i, (var, label) in enumerate(zip(closed.jaxpr.invars, in_labels)):
        if label is None or label.axis is None:
            in_vals.append(CLEAN)
            continue
        shape = var.aval.shape
        assert 0 <= label.axis < len(shape), \
            f"label axis {label.axis} out of range for invar {i} {shape}"
        lanes = VARIANT if label.lanes == "variant" \
            else Known(_np_scalar(label.lanes, var.aval.dtype))
        in_vals.append(AbsVal(
            digits=(Digit(label.axis, 1, shape[label.axis]),), lanes=lanes))
    outs = interp.run_jaxpr(closed.jaxpr, in_vals, "")
    n_poisoned = 0
    for i, o in enumerate(outs):
        if o.poison:
            n_poisoned += 1
            report.add("taint.poisoned-output", Severity.ERROR,
                       f"{program}:out[{i}]",
                       f"output {i} is influenced by taint violations: "
                       f"{sorted(o.poison)}")
    for i, contract in (out_contracts or {}).items():
        o = outs[i]
        ok = any(d.axis == contract.axis for d in o.digits) and \
            isinstance(o.lanes, Known) and \
            float(np.asarray(o.lanes.value)) == float(contract.value)
        # an unmarked constant output equal to the contract also satisfies
        ok = ok or (not o.marked and o.const is not None
                    and float(o.const) == float(contract.value))
        if not ok:
            report.add("taint.output-contract", Severity.ERROR,
                       f"{program}:out[{i}]",
                       f"output {i} must hold Known({contract.value}) on "
                       f"padded lanes of axis {contract.axis}; analysis "
                       f"derived digits={o.digits} lanes={o.lanes}")
    for text in sorted(interp.assumptions):
        report.add("taint.assumption", Severity.INFO, program, text)
    report.programs[program] = {
        "pass": "taint",
        "n_eqns": interp.n_eqns,
        "n_certified_reductions": interp.n_certified,
        "n_outputs": len(outs),
        "n_poisoned_outputs": n_poisoned,
        "assumptions": sorted(interp.assumptions),
        "ok": not any(f.severity is Severity.ERROR
                      for f in report.findings
                      if f.where.startswith(program)),
    }
    return report

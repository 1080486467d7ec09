"""Jaxpr/lowered-HLO contract rules for registered hot entry points.

Each registered ``EntrySpec`` (see ``registry``) is traced at its pinned
abstract shapes -- ``jax.make_jaxpr`` for the jaxpr-level rules, and
``.lower()`` for the StableHLO-level ones -- and every rule in ``RULES``
runs over the collected artifacts. Nothing executes: tracing + lowering
are symbolic, so the whole check suite is a few seconds of CPU and runs
unchanged on a machine with no accelerator.

The rules encode the invariants this repo's hot paths have been tuned
around (and that regressed silently at least once each before being
pinned here):

  host-callback             no pure/io/debug callbacks inside a jitted
                            hot body (a host round-trip per step).
  trace-transfer            tracing+lowering succeed under
                            ``jax.transfer_guard("disallow")`` -- no
                            implicit host<->device transfer is baked
                            into the traced program.
  donation-declared         entries that promise aliasing (``must_alias``)
                            actually declare donation on their shipped
                            jit wrapper.
  donation-surviving        declared donations survive lowering as real
                            input/output aliases -- XLA silently drops
                            donations with no shape/dtype-matching
                            output (a UserWarning at lowering is the
                            only trace), which turns an in-place state
                            update into a fresh allocation per step.
  float64-leak              no float64 output, and no weakly-typed
                            carried state (a Python-scalar weak type in
                            the carry changes the aval between steps =>
                            a recompile per step).
  carry-stable              carried-state output avals are EXACTLY the
                            input avals (shape, dtype, weak type) --
                            the steady-state no-recompile condition.
  pallas-tile-divides       every Pallas BlockSpec tile divides its
                            array dim (a ragged tile means masked
                            partial blocks, or miscompiles on backends
                            that assume divisibility).
  pallas-narrow-output-tile an output BlockSpec whose lane (last) dim is
                            < 128 -- the known narrow-tile TPU lowering
                            caveat (e.g. the forest kernel's
                            ``(block_b, n_classes=2)`` vote tile);
                            deliberate cases carry a suppression with
                            the reason + validation story.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import jax

from repro.analysis.base import Violation
from repro.analysis.registry import EntrySpec

_CALLBACK_PRIMITIVES = {"pure_callback", "io_callback", "debug_callback"}
_DROPPED_DONATION_MSG = "donated buffers were not usable"
_LANE = 128  # TPU lane width the narrow-tile rule is calibrated to


# ---------------------------------------------------------------------------
# Artifact collection: one trace + one lowering per entry, shared by all
# rules.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TraceArtifacts:
    entry: EntrySpec
    jaxpr: object | None = None          # ClosedJaxpr
    out_shape: object | None = None      # pytree of ShapeDtypeStruct
    lowered_text: str | None = None      # StableHLO
    warnings: list[str] = dataclasses.field(default_factory=list)
    trace_error: str | None = None


def _callable(entry: EntrySpec):
    """The entry's fn with statics bound (positional avals remain)."""
    if entry.static_kwargs:
        return functools.partial(entry.fn, **entry.static_kwargs)
    return entry.fn


def _lowerable(entry: EntrySpec):
    """Something with ``.lower`` carrying the SHIPPED donation story."""
    if entry.is_jitted:
        return entry.fn
    return jax.jit(
        functools.partial(entry.fn, **entry.static_kwargs),
        donate_argnums=entry.donate_argnums,
    )


def collect_artifacts(entry: EntrySpec) -> TraceArtifacts:
    art = TraceArtifacts(entry=entry)
    fn = _callable(entry)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            with jax.transfer_guard("disallow"):
                art.jaxpr, art.out_shape = jax.make_jaxpr(
                    fn, return_shape=True
                )(*entry.args)
                lowerable = _lowerable(entry)
                if entry.is_jitted:
                    lowered = lowerable.lower(
                        *entry.args, **entry.static_kwargs
                    )
                else:
                    lowered = lowerable.lower(*entry.args)
                art.lowered_text = lowered.as_text()
        except Exception as e:  # noqa: BLE001 -- reported per-entry below
            msg = str(e)
            if "transfer" in msg.lower():
                art.trace_error = msg
            else:
                raise RuntimeError(
                    f"contract tracing failed for {entry.name}"
                ) from e
    art.warnings = [str(w.message) for w in caught]
    return art


# ---------------------------------------------------------------------------
# Jaxpr walking helpers.
# ---------------------------------------------------------------------------

def _subjaxprs(params: dict):
    for val in params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if hasattr(v, "eqns"):  # raw Jaxpr
                yield v
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield v.jaxpr  # ClosedJaxpr


def iter_eqns(jaxpr):
    """All eqns of a (Closed)Jaxpr, recursing into nested jaxprs."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def _flat_slice(trees, index):
    """(start, stop) of ``trees[index]``'s leaves in the flat leaf list."""
    start = sum(len(jax.tree_util.tree_leaves(t)) for t in trees[:index])
    return start, start + len(jax.tree_util.tree_leaves(trees[index]))


def _carry_avals(art: TraceArtifacts):
    """(in_avals, out_avals) of the entry's carried state, or None."""
    entry = art.entry
    if entry.carry is None or art.jaxpr is None:
        return None
    argnum, out_index = entry.carry
    i0, i1 = _flat_slice(list(entry.args), argnum)
    in_avals = art.jaxpr.in_avals[i0:i1]
    if out_index is None:
        out_avals = list(art.jaxpr.out_avals)
    else:
        outs = list(art.out_shape)
        o0, o1 = _flat_slice(outs, out_index)
        out_avals = art.jaxpr.out_avals[o0:o1]
    return in_avals, out_avals


# ---------------------------------------------------------------------------
# Rules. Each maps TraceArtifacts -> list[Violation].
# ---------------------------------------------------------------------------

def rule_host_callback(art: TraceArtifacts):
    if art.jaxpr is None:
        return []
    out = []
    for eqn in iter_eqns(art.jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMITIVES:
            cb = eqn.params.get("callback", "")
            out.append(Violation(
                rule="host-callback",
                subject=art.entry.name,
                message=(
                    f"{eqn.primitive.name} inside the traced body "
                    f"({cb!r}): a host round-trip on every step"
                ),
            ))
    return out


def rule_trace_transfer(art: TraceArtifacts):
    if art.trace_error is not None:
        return [Violation(
            rule="trace-transfer",
            subject=art.entry.name,
            message=(
                "tracing under jax.transfer_guard('disallow') raised: "
                + art.trace_error.splitlines()[0]
            ),
        )]
    return []


def _alias_count(text: str) -> int:
    return text.count("tf.aliasing_output")


def rule_donation_declared(art: TraceArtifacts):
    entry = art.entry
    if not entry.must_alias or art.lowered_text is None:
        return []
    if _alias_count(art.lowered_text) == 0 and not any(
        _DROPPED_DONATION_MSG in w for w in art.warnings
    ):
        return [Violation(
            rule="donation-declared",
            subject=entry.name,
            message=(
                f"argnums {entry.must_alias} must alias their outputs but "
                "the shipped jit wrapper declares no donation (no "
                "tf.aliasing_output in the lowered module, no dropped-"
                "donation warning)"
            ),
        )]
    return []


def rule_donation_surviving(art: TraceArtifacts):
    entry = art.entry
    out = []
    for w in art.warnings:
        if _DROPPED_DONATION_MSG in w:
            out.append(Violation(
                rule="donation-surviving",
                subject=entry.name,
                message=(
                    "XLA dropped a declared donation at lowering ("
                    + w.splitlines()[0].strip()
                    + "): the buffer is copied, not reused -- drop the "
                    "donation or restructure so an output aliases it"
                ),
            ))
    if entry.must_alias and art.lowered_text is not None and not out:
        expected = sum(
            len(jax.tree_util.tree_leaves(entry.args[i]))
            for i in entry.must_alias
        )
        got = _alias_count(art.lowered_text)
        if 0 < got < expected:
            out.append(Violation(
                rule="donation-surviving",
                subject=entry.name,
                message=(
                    f"only {got}/{expected} donated leaves survived "
                    "lowering as input/output aliases"
                ),
            ))
    return out


def rule_float64_leak(art: TraceArtifacts):
    if art.jaxpr is None:
        return []
    out = []
    for i, aval in enumerate(art.jaxpr.out_avals):
        if str(getattr(aval, "dtype", "")) == "float64":
            out.append(Violation(
                rule="float64-leak",
                subject=art.entry.name,
                message=(
                    f"output {i} is float64 ({aval.str_short()}): a "
                    "silent 2x memory/bandwidth promotion on the hot path"
                ),
            ))
    carry = _carry_avals(art)
    if carry is not None:
        _, out_avals = carry
        for i, aval in enumerate(out_avals):
            if getattr(aval, "weak_type", False):
                out.append(Violation(
                    rule="float64-leak",
                    subject=art.entry.name,
                    message=(
                        f"carried-state output leaf {i} is weakly typed "
                        f"({aval.str_short()}): a Python scalar reached "
                        "the carry, so the aval changes across steps"
                    ),
                ))
    return out


def rule_carry_stable(art: TraceArtifacts):
    carry = _carry_avals(art)
    if carry is None:
        return []
    in_avals, out_avals = carry
    out = []
    if len(in_avals) != len(out_avals):
        return [Violation(
            rule="carry-stable",
            subject=art.entry.name,
            message=(
                f"carried state has {len(in_avals)} input leaves but "
                f"{len(out_avals)} output leaves"
            ),
        )]
    for i, (a, b) in enumerate(zip(in_avals, out_avals)):
        same = (
            a.shape == b.shape
            and a.dtype == b.dtype
            and getattr(a, "weak_type", False)
            == getattr(b, "weak_type", False)
        )
        if not same:
            out.append(Violation(
                rule="carry-stable",
                subject=art.entry.name,
                message=(
                    f"carried-state leaf {i} changes aval across the "
                    f"step: {a.str_short()} -> {b.str_short()} "
                    "(weak-type/dtype/shape drift = recompile per step)"
                ),
            ))
    return out


def _pallas_calls(art: TraceArtifacts):
    if art.jaxpr is None:
        return
    for eqn in iter_eqns(art.jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params.get("name", "pallas_call")
            gm = eqn.params.get("grid_mapping")
            if gm is not None:
                yield name, gm


def _int_block_dims(block_mapping):
    """(block_shape ints aligned to array dims) for one BlockMapping."""
    block = tuple(block_mapping.block_shape)
    array = tuple(block_mapping.array_aval.shape)
    # Blocked dims carry their ``block_size``; squeezed dims have none
    # and pair as None. Align from the right, which is how Pallas pairs
    # them.
    pairs = []
    for b, d in zip(block[::-1], array[::-1]):
        size = getattr(b, "block_size", b)
        pairs.append((size if isinstance(size, int) else None, d))
    return pairs[::-1]


def rule_pallas_tile_divides(art: TraceArtifacts):
    out = []
    for kname, gm in _pallas_calls(art):
        mappings = list(getattr(gm, "block_mappings", ()))
        for bi, bm in enumerate(mappings):
            for di, (b, d) in enumerate(_int_block_dims(bm)):
                if b is None or b <= 0:
                    continue
                if d % b != 0 and b < d:
                    out.append(Violation(
                        rule="pallas-tile-divides",
                        subject=art.entry.name,
                        message=(
                            f"kernel {kname!r} operand {bi} dim {di}: "
                            f"tile {b} does not divide array dim {d} "
                            "(ragged partial blocks)"
                        ),
                    ))
    return out


def rule_pallas_narrow_output_tile(art: TraceArtifacts):
    out = []
    for kname, gm in _pallas_calls(art):
        for bi, bm in enumerate(getattr(gm, "block_mappings_output", ())):
            dims = _int_block_dims(bm)
            if not dims:
                continue
            b, _ = dims[-1]
            if b is not None and b < _LANE:
                out.append(Violation(
                    rule="pallas-narrow-output-tile",
                    subject=art.entry.name,
                    message=(
                        f"kernel {kname!r} output {bi} lane dim is "
                        f"{b} (< {_LANE}): narrow output tile -- the "
                        "TPU lowering caveat class; needs interpret-"
                        "mode parity coverage and a suppression "
                        "documenting the validation story"
                    ),
                ))
    return out


RULES = {
    "host-callback": rule_host_callback,
    "trace-transfer": rule_trace_transfer,
    "donation-declared": rule_donation_declared,
    "donation-surviving": rule_donation_surviving,
    "float64-leak": rule_float64_leak,
    "carry-stable": rule_carry_stable,
    "pallas-tile-divides": rule_pallas_tile_divides,
    "pallas-narrow-output-tile": rule_pallas_narrow_output_tile,
}


def check_entry(entry: EntrySpec) -> list[Violation]:
    """Trace one entry and run every contract rule over it."""
    art = collect_artifacts(entry)
    violations: list[Violation] = []
    for rule in RULES.values():
        violations.extend(rule(art))
    return violations


def check_registry(entries) -> tuple[list[Violation], list[dict]]:
    """Check every entry; returns (violations, per-entry report rows)."""
    violations: list[Violation] = []
    rows: list[dict] = []
    for entry in entries:
        found = check_entry(entry)
        violations.extend(found)
        rows.append({
            "entry": entry.name,
            "description": entry.description,
            "rules": sorted(RULES),
            "violations": len(found),
        })
    return violations, rows

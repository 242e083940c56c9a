"""The named controls of ``benchmarks/harness/kind_backlog_window.py``:
the program (or, for the window's edge, the reference) made wrong in one
stated way, run through the SAME runner, to see which limit of the
comparison with the float32 reference reads it (``PERF.md`` §6, PR 39;
``tests/benchmark_harness/test_smallthinker_cell.py`` drives the same
patches at toy widths).  Not part of the benchmark and not a way to
serve the model.

    python scripts/smallthinker_controls.py [--seconds S] [--seed N]
        [--rps R] [--chunk C] [--slots B] <control> ...

runs the cell ``smallthinker_longdoc_backlog`` once per named control
(``sound`` is the program as it is), each in a process of its own (a
chip belongs to one process), and prints one JSON line each.  The runs
themselves are ``scripts/kanana_controls.py``'s.  ``reference_variants``
is ONE sound run checked against the reference as it is and as each
control of the reference's side makes it (:data:`REFERENCE_SIDE`), so
that those cost no run of their own.  Every run also reports its
``holds``: the collections of Python's cyclic collector that took over
50 ms, and the spans of over 0.5 s in which the scheduler's thread sat
in one place or held the interpreter (:class:`HoldWatch`), with where.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import traceback
from pathlib import Path
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[1]
for path in (ROOT, ROOT / "scripts"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import kanana_controls as runs                                  # noqa: E402
from kanana_controls import _bf16                               # noqa: E402

CELL = "smallthinker_longdoc_backlog"


def _window_one_off(setattr_, model):
    """The window's edge off by one key: the program attends ``W`` keys
    where the REFERENCE is given ``W + 1`` (a ring of ``W`` cannot hold
    one more, so the fault is put on the other side of the comparison:
    what is measured is whether the comparison tells the two apart)."""
    from benchmarks.reference import smallthinker as reference

    forward = reference.forward_logits

    def wider(*args, **kwargs):
        return forward(*args, **{**kwargs,
                                 "window": model["sliding_window"] + 1})

    setattr_(reference, "forward_logits", wider)


def _no_window(setattr_, model):
    """The window layers attending the whole prefix: the REFERENCE given
    a window as long as a slot (a ring of ``W`` cannot hold more, so the
    fault is put on the other side of the comparison)."""
    from benchmarks.reference import smallthinker as reference

    forward = reference.forward_logits

    def whole(*args, **kwargs):
        return forward(*args, **{**kwargs, "window": 1 << 20})

    setattr_(reference, "forward_logits", whole)


def _reference_not_rotated(setattr_, model):
    """The window layers without their rotary positions, on the
    reference's side (``window_not_rotated`` is the program's)."""
    from benchmarks.reference import smallthinker as reference

    forward = reference.forward_logits

    def flat(params, ids, model_, **kwargs):
        return forward(params, ids, {**model_, "rope_layout":
                                     [0] * len(model_["layer_types"])},
                       **kwargs)

    setattr_(reference, "forward_logits", flat)


# the controls that change only the reference: one sound run can be
# checked against each
REFERENCE_SIDE = ("window_one_off", "no_window", "reference_not_rotated")


def _reference_variants(setattr_, model):
    """The program as it is, its probes checked against the reference
    and then against each of :data:`REFERENCE_SIDE` in turn; a variant's
    faults and readings come back under its name (``no_window: window
    0.5 from the reference ...``, ``no_window.window``)."""
    from benchmarks.harness import kind_backlog_window as runner
    from benchmarks.reference import smallthinker as reference

    check, forward = runner.check_outputs, reference.forward_logits

    def every(engine, cell):
        faults, worst = check(engine, cell)
        for name in REFERENCE_SIDE:
            print(f"[benchmark] control {name}:", file=sys.stderr)
            CONTROLS[name](setattr, model)
            try:
                more, read = check(engine, cell)
            finally:
                reference.forward_logits = forward
            faults += [f"{name}: {fault}" for fault in more]
            worst.update({f"{name}.{key}": value
                          for key, value in read.items()})
        return faults, worst

    setattr_(runner, "check_outputs", every)


class HoldWatch:
    """Where the scheduler's thread was when a run held still.  The
    cyclic collector's passes over 50 ms (``gc.callbacks``), and a
    sampler thread that looks at the watched thread's stack every 10 ms:
    a span of over 0.5 s with the same innermost frames (the thread sat
    there: a wait, a call that did not return) or with no sample at all
    (the sampler could not run: the interpreter was held).  Times are
    seconds from the watch's start."""

    EVERY_S, HELD_S, COLLECTION_S, FRAMES = 0.01, 0.5, 0.05, 10

    def __init__(self) -> None:
        self.watched = threading.get_ident()
        self.t0 = time.perf_counter()
        self.collections: list[dict] = []
        self.holds: list[dict] = []
        self._began = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True,
                                        name="hold-watch")

    def _collected(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._began = now
        elif now - self._began > self.COLLECTION_S:
            self.collections.append({
                "at_s": round(self._began - self.t0, 3),
                "took_s": round(now - self._began, 3),
                "generation": info.get("generation"),
                "collected": info.get("collected")})

    def _stack(self) -> list[str]:
        frame = sys._current_frames().get(self.watched)
        if frame is None:
            return []
        return [f"{Path(f.filename).name}:{f.lineno} {f.name}" for f in
                traceback.extract_stack(frame)[-self.FRAMES:]]

    def _sample(self) -> None:
        last = time.perf_counter()
        stack, since = self._stack(), last
        while not self._stop.wait(self.EVERY_S):
            now = time.perf_counter()
            if now - last > self.HELD_S:
                self.holds.append({"at_s": round(last - self.t0, 3),
                                   "took_s": round(now - last, 3),
                                   "how": "no sample", "stack": stack})
            here = self._stack()
            if here != stack:
                if last - since > self.HELD_S:
                    self.holds.append({"at_s": round(since - self.t0, 3),
                                       "took_s": round(last - since, 3),
                                       "how": "same stack", "stack": stack})
                stack, since = here, now
            last = now
        if last - since > self.HELD_S:
            self.holds.append({"at_s": round(since - self.t0, 3),
                               "took_s": round(last - since, 3),
                               "how": "same stack", "stack": stack})

    def __enter__(self) -> "HoldWatch":
        gc.callbacks.append(self._collected)
        self._thread.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._collected)

    def report(self, since_s: float, until_s: float) -> dict:
        """What fell between ``since_s`` and ``until_s`` (the measured
        window: the warm-up's compilations hold the thread too)."""
        def inside(event: dict) -> bool:
            return (since_s <= event["at_s"] + event["took_s"]
                    and event["at_s"] <= until_s)

        return {"window_s": [round(since_s, 3), round(until_s, 3)],
                "collections": [c for c in self.collections if inside(c)],
                "holds": [h for h in self.holds if inside(h)]}


def _watched(run_one: Callable[..., dict]) -> Callable[..., dict]:
    """``run_one`` that also reports its window's holds (the window
    opens ``setup_s`` after ``run_one`` began and lasts ``wall_s``)."""
    def run(*args: Any, **kwargs: Any) -> dict:
        with HoldWatch() as watch:
            result = run_one(*args, **kwargs)
        since = result["setup_s"]
        return {**result, "holds": watch.report(
            since, since + (result.get("wall_s") or 0.0))}
    return run


def _with_block(change: Callable[[Any, str, Any], dict]):
    """Every layer run with ``change(config, kind, rotary)``'s keyword
    arguments to the block (the weights and the reference keep the true
    configuration)."""
    def patch(setattr_, model):
        from dlbb_tpu.models import hybrid

        block = hybrid.hybrid_block

        def wrong(h, layer, kind, config, *rest, **kw):
            config, kw = change(config, kind, kw)
            return block(h, layer, kind, config, *rest, **kw)

        setattr_(hybrid, "hybrid_block", wrong)
    return patch


def _bf16_softmax_route(setattr_, model):
    """The router's product and softmax rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    from dlbb_tpu.ops import routed_experts as moe

    def route(u, w_router, bias, top_k, scale, scoring="sigmoid"):
        logits = _bf16(jnp.einsum("th,he->te", u.astype(jnp.float32),
                                  w_router.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST))
        scores = _bf16(jax.nn.softmax(logits, axis=-1))
        _, experts = jax.lax.top_k(scores, top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        gates = _bf16(chosen / jnp.sum(chosen, axis=-1, keepdims=True))
        return moe.Routing(experts.astype(jnp.int32), gates, scores)

    setattr_(moe, "route", route)


def chunk_attention_bf16(qh, k_all, v_all, start, scale=None, mask=None):
    """``serve/attend.py::_chunk_attention`` (grouped heads, an explicit
    mask or the offset-causal one) with its scores, its softmax and its
    output rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    b, n, c, d = qh.shape
    kvh = k_all.shape[1]
    q32 = qh.astype(jnp.float32).reshape(b, kvh, n // kvh, c, d)
    k32 = k_all.transpose(1, 0, 2).astype(jnp.float32)[None]
    v32 = v_all.transpose(1, 0, 2).astype(jnp.float32)[None]
    if mask is None:
        mask = (jnp.arange(k_all.shape[0])[None, :]
                <= (start + jnp.arange(c))[:, None])
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, k32)
    logits = _bf16(logits / d ** 0.5 if scale is None else logits * scale)
    probs = _bf16(jax.nn.softmax(
        jnp.where(mask[None, None, None], logits, -jnp.inf), axis=-1))
    out = _bf16(jnp.einsum("bhgqk,bhkd->bhgqd", probs, v32))
    return out.reshape(b, n, c, d).astype(k_all.dtype)


def _float32_parts_bfloat16(setattr_, model):
    """The nearest precision below the configuration's: everything it
    states as float32 (the router and its softmax, the norms, the
    combine, a prompt chunk's scores and softmax) computed in bfloat16,
    each product rounded by ``reduce_precision``.  (The decode kernel's
    softmax, a chunk's past ``attend.CHUNK_SCORES_BYTES`` of scores,
    which runs as the ``chunk_attend`` kernel, and the ReGLU's activation
    sit inside a kernel and a fusion, with no seam for a control.)"""
    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid

    _bf16_softmax_route(setattr_, model)
    runs._norms_combine_softmax_bfloat16(setattr_, model)
    setattr_(hybrid, "rmsnorm", runs.rmsnorm_bf16)
    setattr_(serve_hybrid, "_chunk_attention", chunk_attention_bf16)


CONTROLS: dict[str, Callable[[Callable, dict], None]] = {
    "sound": lambda setattr_, model: None,
    # the window's edge one key off, no window at all, no rotary (the
    # reference's side), and one run checked against all three
    "window_one_off": _window_one_off,
    "no_window": _no_window,
    "reference_not_rotated": _reference_not_rotated,
    "reference_variants": _reference_variants,
    # the window layers without their rotary positions
    "window_not_rotated": _with_block(
        lambda config, kind, kw: (config, {**kw, "rotary": False})),
    # the router reading the MLP's input, as every other family's does
    "router_after_attention": _with_block(
        lambda config, kind, kw: (
            config.with_(router_before_attention=False), kw)),
    # SwiGLU experts where the model's are ReGLU
    "swiglu_experts": _with_block(
        lambda config, kind, kw: (config.with_(mlp="swiglu"), kw)),
    # the nearest precision below the configuration's, and the router
    # alone so
    "float32_parts_bfloat16": _float32_parts_bfloat16,
    "router_bfloat16": _bf16_softmax_route,
}


def apply(name: str, setattr_: Callable[[Any, str, Any], None],
          model: dict) -> None:
    """Make the program wrong as ``name`` says, through ``setattr_``
    (``monkeypatch.setattr`` in a test, ``setattr`` in a process that
    ends with the run)."""
    CONTROLS[name](setattr_, model)


if __name__ == "__main__":
    runs.run_one = _watched(runs.run_one)
    sys.exit(runs.main(script=__file__, cell=CELL, controls=CONTROLS,
                       log="smallthinker_controls.jsonl", doc=__doc__))

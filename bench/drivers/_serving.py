"""The served forecast program held hot in a ``ServingEngine``, the seeded
request states, one request's life as its client sees it, and the check of
the served answers.

Configuration keys: ``domain``, ``member_counts``, ``scalars``, ``states``
(distinct initial states, one per ensemble member), ``limits``.
"""

from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from bench import harness, reference

HALO = 1  # the forecast program's read extent


def _init(key, ni, nj, nk, states):
    """Winds and the request states on the device: a Gaussian blob at a
    random place per state, plus noise; winds turning with position, so the
    upwind branch takes both signs."""
    import jax
    import jax.numpy as jnp

    shape = (ni + 2 * HALO, nj + 2 * HALO, nk)
    k_centre, k_noise, k_phase = jax.random.split(key, 3)
    x = jnp.linspace(-1.0, 1.0, shape[0])[:, None, None]
    y = jnp.linspace(-1.0, 1.0, shape[1])[None, :, None]
    z = jnp.linspace(0.0, 1.0, nk)[None, None, :]
    centre = jax.random.uniform(k_centre, (states, 2), minval=-0.5, maxval=0.5)
    noise = jax.random.normal(k_noise, (states,) + shape)
    blobs = jnp.stack([
        jnp.exp(-8.0 * ((x - centre[s, 0]) ** 2 + (y - centre[s, 1]) ** 2)) * (1.0 + 0.1 * z)
        + 1e-3 * noise[s]
        for s in range(states)
    ])
    phase = 2 * math.pi * jax.random.uniform(k_phase, (2,))
    u = jnp.broadcast_to(0.8 * jnp.sin(math.pi * y + phase[0]), shape)
    v = jnp.broadcast_to(0.8 * jnp.cos(math.pi * x + phase[1]), shape)
    return blobs, u, v


@dataclass
class Outcome:
    """One request as its client saw it."""

    due: float
    steps: int
    state: int
    done: Optional[float] = None
    error: Optional[str] = None
    final: Optional[np.ndarray] = None
    #: padded member count of the batch that served the final step
    members: Optional[int] = None

    @property
    def latency_s(self) -> float:
        return self.done - self.due if self.done is not None and self.error is None else math.inf


@dataclass
class Served:
    """The engine with the forecast program registered and warmed."""

    engine: Any
    entry: Any
    states: np.ndarray
    u: Any
    v: Any
    scalars: Dict[str, float]
    stream_every: int
    outcomes: List[Outcome] = field(default_factory=list)

    def submit(self, due: float, steps: int, state: int):
        """Submit one request; returns its outcome and the coroutine that
        drains its stream (``None`` when admission refused it)."""
        from repro.serving import ServingError

        o = Outcome(due=due, steps=steps, state=state)
        self.outcomes.append(o)
        try:
            req = self.engine.submit(self.entry.name, {"phi": self.states[state]}, steps=steps,
                                     stream_every=self.stream_every)
        except ServingError as e:
            o.error = f"{e.code}: {e.reason}"
            return o, None
        return o, self._drain(req, o)

    async def _drain(self, req, o: Outcome) -> None:
        async for ev in self.engine.stream(req):
            if ev["type"] == "step" and ev["step"] == o.steps:
                o.final = ev["fields"]["phi"]
                o.members = ev["batch"]["members"]
            elif ev["type"] == "done":
                o.done = time.perf_counter()
            elif ev["type"] == "error":
                o.done = time.perf_counter()
                o.error = f"{ev.get('code')}: {ev.get('reason')}"


def serve(cell: harness.Cell, seed: int, trace: bool) -> Served:
    import jax

    from repro.core import storage
    from repro.obs import trace as otrace
    from repro.serving import ServingEngine
    from repro.stencils.forecast import build_forecast_step

    cfg = cell.config
    ni, nj, nk = (int(d) for d in cfg["domain"])
    init = jax.jit(functools.partial(_init, ni=ni, nj=nj, nk=nk, states=int(cfg["states"])))
    blobs, u, v = init(jax.random.key(harness.seed_ints(seed, 1)[0]))
    states = np.asarray(blobs)
    del blobs
    shape = states.shape[1:]

    def st(arr):
        return storage.Storage(arr, backend="jax", default_origin=(HALO, HALO, 0))

    zeros = jax.numpy.zeros(shape)
    template = {"phi": st(states[0]), "u": st(u), "v": st(v), "adv": st(zeros), "phi_star": st(zeros),
                "phi_new": st(zeros)}
    tracer = otrace.Tracer(enabled=True, capacity=1 << 20) if trace else None
    engine = ServingEngine(tracer=tracer, jax_profile=trace)
    stream_every = int(cell.traffic["stream_every"])
    entry = engine.register(
        build_forecast_step("jax", (ni, nj, nk)), fields=template, scalars=dict(cfg["scalars"]),
        request_fields=("phi",), member_counts=tuple(cfg["member_counts"]), warm=True,
        warm_chunk=stream_every,
    )
    return Served(engine=engine, entry=entry, states=states, u=u, v=v, scalars=dict(cfg["scalars"]),
                  stream_every=stream_every)


def engine_counters(served: Served) -> Dict[str, float]:
    """The engine's cumulative counts that the per-layer metrics difference
    over the window."""
    h = served.entry.hist["queue_wait"]
    c = served.entry.counters
    return {"queue_wait_n": h.count, "queue_wait_sum_s": h.sum,
            "live_members": c["live_members"].value, "padded_members": c["padded_members"].value}


def record(cell, served: Served, before, after, window, trace_out) -> harness.Record:
    """The run's record: counters differenced over the window, the host
    spans of scatter and gather, and the memory peak."""
    import jax

    rec = harness.Record(cell=cell, device_kind=jax.devices()[0].device_kind)
    rec.trace = trace_out.get("trace")
    rec.counters.update({k: after[k] - before[k] for k in before})
    t0, t1 = window
    if served.engine._tracer is not None:
        io = [s["end_s"] - s["start_s"] for s in served.engine._tracer.snapshot()
              if s["name"] in ("serving.scatter", "serving.gather") and t0 <= s["start_s"] <= t1]
        rec.counters["host_io_s"] = float(np.sum(io))
    rec.memory_peak_bytes = harness.memory_peak_bytes(jax.devices()[:1])
    return rec


def check(cell: harness.Cell, served: Served) -> List[harness.Check]:
    """The final ``phi`` of every completed request against the float64
    reference from its initial state, computed once per state (with
    ``cell.control``, the float32 reference read in the program's place too).
    A request that completed without its final step fails the check."""
    u, v = np.asarray(served.u), np.asarray(served.v)
    limit = float(cell.config["limits"]["forecast_err"])
    low = harness.lower_precision(cell.config["dtype"])
    refs: Dict[tuple, np.ndarray] = {}
    err = control = 0.0
    for o in served.outcomes:
        if o.error is not None or o.done is None:
            continue
        key = (o.state, o.steps)
        if key not in refs:
            refs[key] = reference.forecast(served.states[o.state], u, v, served.scalars, o.steps)
            if cell.control:
                got = reference.forecast(served.states[o.state], u, v, served.scalars, o.steps, low)
                control = max(control, reference.rel_err(got, refs[key]))
        err = max(err, reference.rel_err(o.final, refs[key]) if o.final is not None else math.inf)
    checks = [harness.Check("forecast_err", err, limit)]
    return checks + ([harness.Check("control.forecast_err", control, limit)] if cell.control else [])


def compared_by_members(served: Served) -> Dict[int, int]:
    """How many compared requests rode in batches of each padded member count."""
    out: Dict[int, int] = {}
    for o in served.outcomes:
        if o.error is None and o.final is not None:
            out[o.members] = out.get(o.members, 0) + 1
    return dict(sorted(out.items()))

"""Driver: a closed loop of forecast requests into ``ServingEngine.submit``.

``clients`` callers, one per ensemble member, each send a request from
their member's initial state, wait for its answer and send the next, until
the window closes; so a slow server receives less load.  Requests sent
before the close run to their end (within ``drain_s``), and the window ends
when the last of them is answered: the rate is every request sent in the
window over that time, so no work is left out and none counted half.  Every
answer is checked.

Traffic keys: ``driver``, ``clients``, ``steps``, ``stream_every``,
``drain_s``.
"""

from __future__ import annotations

import asyncio
import math
import sys
import time

from bench import harness

from . import _serving


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool) -> harness.Record:
    import jax

    traffic = cell.traffic
    served = _serving.serve(cell, seed, trace)
    clients, steps = int(traffic["clients"]), int(traffic["steps"])
    nstates = len(served.states)
    out: dict = {}

    async def client(c: int, close: float):
        while time.perf_counter() < close:
            o, drain = served.submit(time.perf_counter(), steps, c % nstates)
            if drain is None:
                return
            await drain

    async def drive():
        async with served.engine:
            with harness.profiled(trace, cell.name, out):
                before = _serving.engine_counters(served)
                setup_s = harness.process_age_s()
                with jax.profiler.TraceAnnotation(harness.WINDOW):
                    t0 = time.perf_counter()
                    tasks = [asyncio.ensure_future(client(c, t0 + seconds)) for c in range(clients)]
                    await asyncio.wait(tasks, timeout=seconds + float(traffic["drain_s"]))
                    t1 = time.perf_counter()
                after = _serving.engine_counters(served)
            for t in tasks:
                if not t.done():
                    t.cancel()
        return setup_s, before, after, (t0, t1)

    setup_s, before, after, window = asyncio.run(drive())
    rec = _serving.record(cell, served, before, after, window, out)
    rec.setup_s = setup_s
    outcomes = served.outcomes
    rec.attempted = len(outcomes)
    rec.failed = sum(1 for o in outcomes if not math.isfinite(o.latency_s))
    done = [o.done for o in outcomes if o.error is None and o.done is not None]
    rec.window_s = (max(done) if done else window[1]) - window[0]
    rec.checks = _serving.check(cell, served)
    by_members = _serving.compared_by_members(served)
    rec.counters.update(completed=len(done), compared=sum(by_members.values()),
                        compared_by_members=by_members)
    print(f"bench: compared {rec.counters['compared']} of {rec.attempted} requests; by the members of the "
          f"batch that served them: {by_members}", file=sys.stderr)
    return rec

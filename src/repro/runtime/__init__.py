"""The forecast server's process supervision: the restart policy, the
supervisor that spawns and probes the server, and the straggler watchdog
the engine times its dispatches with (``supervise.py``)."""

from .supervise import (
    RestartPolicy,
    StragglerWatchdog,
    Supervisor,
    SupervisorGaveUp,
    WatchdogStats,
    http_ready,
)

__all__ = [
    "RestartPolicy",
    "StragglerWatchdog",
    "Supervisor",
    "SupervisorGaveUp",
    "WatchdogStats",
    "http_ready",
]

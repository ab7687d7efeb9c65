"""Shedline — the hardened serving front end (ISSUE 12).

Host-side request admission over ``generation.make_instrumented_generate_fn``:
a bounded, deadline-aware admission queue with first-class load shedding
(``serving.frontend.RequestFrontEnd``), mid-decode deadline enforcement and
cancellation through the ``on_token`` streaming seam, an error-rate/
sentinel-fed circuit breaker with RetryPolicy-spaced half-open probes
(``serving.breaker``), bounded retry for transient pre-decode failures,
graceful SIGTERM drain, and the clean-books invariant — every submitted
request reaches exactly one terminal outcome
(``ok | error | timeout | shed | cancelled``), auditable via
``RequestFrontEnd.books()``. ``serving.faultinject`` provides the
deterministic fault injector and manual clock ``tools/chaos.py``'s
``serve_*`` scenarios certify the whole shell with. ``serving.router``
(Fleetline) runs N engine replicas behind one submit surface with
least-outstanding dispatch, drain/join, and journal-backed failover.

See docs/robustness.md#serving-hardening.
"""

from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.serving.breaker import (  # noqa: F401
    STATE_VALUES,
    BreakerConfig,
    CircuitBreaker,
)
from perceiver_io_tpu.serving.faultinject import (  # noqa: F401
    EngineCrash,
    FaultInjector,
    InjectedFault,
    ManualClock,
    poison_params,
)
from perceiver_io_tpu.serving.journal import (  # noqa: F401
    JOURNAL_KINDS,
    RequestJournal,
)
from perceiver_io_tpu.serving.engine import (  # noqa: F401
    EngineConfig,
    EngineFrontEnd,
)
from perceiver_io_tpu.serving.frontend import (  # noqa: F401
    SHED_REASONS,
    TERMINAL_OUTCOMES,
    FrontEndConfig,
    FrontEndRecord,
    DecodePathFailure,
    RequestFrontEnd,
)
from perceiver_io_tpu.serving.pages import (  # noqa: F401
    PageAllocator,
    PageGrant,
    PageStats,
)
from perceiver_io_tpu.serving.router import (  # noqa: F401
    FleetConfig,
    FleetRouter,
    ReplicaHandle,
)

__all__ = [
    "EngineConfig",
    "EngineCrash",
    "EngineFrontEnd",
    "JOURNAL_KINDS",
    "RequestJournal",
    "PageAllocator",
    "PageGrant",
    "PageStats",
    "STATE_VALUES",
    "BreakerConfig",
    "CircuitBreaker",
    "FaultInjector",
    "InjectedFault",
    "ManualClock",
    "poison_params",
    "SHED_REASONS",
    "TERMINAL_OUTCOMES",
    "FrontEndConfig",
    "FrontEndRecord",
    "DecodePathFailure",
    "RequestFrontEnd",
    "FleetConfig",
    "FleetRouter",
    "ReplicaHandle",
]

_STARTUP.close(_IMPORTING)

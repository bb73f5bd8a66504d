"""Continuous-batching ensemble server: simulation-as-a-service.

The member axis ``B`` (the round-7 batched ensemble substrate) is an
inference-style batch dimension; this package feeds it the way LLM
servers feed theirs — independent scenario requests (IC family,
perturbation seed, run length, output subset) packed into one batched
stepper whose compiled executables stay warm across requests, with
per-member run-length masking so a finished member's slot is refilled
from the request queue at the next segment boundary instead of idling
until the slowest member drains (ROADMAP open item 1; docs/USAGE.md
"Serving", docs/DESIGN.md "Continuous batching").

Round 12 adds multi-chip serving: the ``serve.placement:`` block maps
each batch-size bucket onto the available devices — member-parallel
(the packed member axis shards across a ``('member',)`` mesh) or
panel-sharded (each request's six faces spread over the
``('panel', 'member')`` mesh through the batched-exchange ensemble
stepper); :mod:`jaxstream.serve.placement` holds the pure planner.

Round 14 adds the network-serving hooks: ``serve_forever`` (the
gateway's drain loop with the per-segment autoscale tick),
``begin_drain`` + :class:`ServerDraining` (graceful shutdown — typed
refusals while in-flight members finish), ``resize`` (live bucket-cap
scaling among warm executables), and ``on_segment`` progress events —
the surface :mod:`jaxstream.gateway` and :mod:`jaxstream.loadgen`
build on.

Round 21 adds the warm-pool subsystem (:mod:`jaxstream.serve.
warmpool`): disk-backed serialized executables keyed by plan + proof +
toolchain so a restarted server loads instead of recompiling, a
persistent compile cache rung, speculative compilation of
adjacent buckets, and :class:`HeadroomRefused` — the first enforcement
consumer of the round-19 advisory ``headroom_frac``.
"""

from .placement import BucketPlan, plan_placement, placement_report
from .queue import (AdmissionRefused, QueueFull, RequestQueue,
                    ServerDraining)
from .request import ScenarioRequest, RequestResult
from .server import EnsembleServer, serve_requests
from .warmpool import (HeadroomRefused, SpeculativeCompiler,
                       WarmExecutable, WarmPool)

__all__ = [
    "AdmissionRefused",
    "BucketPlan",
    "EnsembleServer",
    "HeadroomRefused",
    "QueueFull",
    "RequestQueue",
    "RequestResult",
    "ScenarioRequest",
    "ServerDraining",
    "SpeculativeCompiler",
    "WarmExecutable",
    "WarmPool",
    "placement_report",
    "plan_placement",
    "serve_requests",
]

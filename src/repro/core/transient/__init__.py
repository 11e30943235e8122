from repro.core.transient.revocation import (  # noqa: F401
    LifetimeModel, REGION_GPU_PARAMS, RevocationSampler,
)
from repro.core.transient.startup import StartupModel  # noqa: F401
from repro.core.transient.replacement import ReplacementModel  # noqa: F401
from repro.core.transient.fleet import FleetEvent, FleetSim  # noqa: F401
from repro.core.transient.draws import FleetDraws  # noqa: F401

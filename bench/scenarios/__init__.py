"""The drill registry. Importing this package registers every scenario
module; ``bench.py`` resolves ``--<name>`` through :func:`run`.
"""

from .registry import REGISTRY, Scenario, get, register, run

# scenario modules self-register on import
from . import serving_reliability   # noqa: F401  (side-effect import)
from . import fleet_kv              # noqa: F401
from . import million_user_day      # noqa: F401
from . import ps_recommender        # noqa: F401
from . import moe_training          # noqa: F401
from . import long_context          # noqa: F401
from . import tracing               # noqa: F401
from . import observability         # noqa: F401
from . import sdc                   # noqa: F401
from . import elastic               # noqa: F401
from . import reliable_step         # noqa: F401
from . import single_chip_speed     # noqa: F401
from . import serving               # noqa: F401
from . import serving_throughput    # noqa: F401
from . import multichip_scaling     # noqa: F401
from . import inject_fault          # noqa: F401
from . import guardrails            # noqa: F401
from . import flight_recorder       # noqa: F401

__all__ = ["REGISTRY", "Scenario", "get", "register", "run"]

"""Self-contained cache-policy objects (FreqCa + family).

Policies implement the four-method protocol in :mod:`.base`; the
diffusion sampler drives them through a per-lane
:class:`~.registry.PolicyBank` and never dispatches on policy names.
Build them directly (``FreqCaPolicy(interval=5)``); ``available()``
lists the ``name`` of every class exported here.
"""
from repro.core.policies.base import (ErrorFeedback, Policy,  # noqa: F401
                                      Ring, StepContext, lane_select)
from repro.core.policies.foca import FoCaPolicy  # noqa: F401
from repro.core.policies.fora import ForaPolicy  # noqa: F401
from repro.core.policies.freqca import FreqCaPolicy  # noqa: F401
from repro.core.policies.freqca_a import FreqCaAdaptivePolicy  # noqa: F401
from repro.core.policies.freqca_eb import (ERROR_TIERS,  # noqa: F401
                                           FreqCaErrorBudgetPolicy,
                                           budget_tier)
from repro.core.policies.none import NoCachePolicy  # noqa: F401
from repro.core.policies.registry import (PolicyBank, available,  # noqa: F401
                                          bank, compatibility_key, resolve)
from repro.core.policies.taylorseer import TaylorSeerPolicy  # noqa: F401
from repro.core.policies.teacache import TeaCachePolicy  # noqa: F401

"""Multi-tenant serving core: admission control + cross-session plan cache.

The serving-side counterpart of the exchange work in the parallel/
package: ``plancache`` amortizes planning across sessions (the compiled
programs are the process stage cache's), ``admission`` bounds what a
shared server accepts (the thriftserver pool-backpressure analog).  ``server.py``
wires both into the HTTP statement path."""

from .admission import (AdmissionController, AdmissionRejected,
                        DemandSignal)
from .plancache import PLANNING_CONF_KEYS, PlanCache, fingerprint

__all__ = ["AdmissionController", "AdmissionRejected", "DemandSignal",
           "PlanCache", "PLANNING_CONF_KEYS", "fingerprint"]

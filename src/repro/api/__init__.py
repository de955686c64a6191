"""Unified clustering API: ``fit()`` over every algorithm and backend.

    from repro.api import fit, list_algorithms
    res = fit(x, k=25)                          # SOCCER, auto backend
    res = fit(x, k=25, algo="kmeans_parallel", rounds=5)
    res.centers, res.rounds, res.uplink_points, res.uplink_bytes,
    res.cost(x)

Algorithms register drivers in ``repro.api.registry``; backends
(virtual single-device, mesh shard_map) implement the ``Backend``
protocol in ``repro.api.backends``.
"""
from repro.api.backends import (Backend, MeshBackend, VirtualBackend,
                                resolve_backend)
from repro.api.registry import (get_algorithm, list_algorithms,
                                register_algorithm)
from repro.api.result import ClusterResult, uplink_bytes
from repro.api.facade import fit, fit_update
from repro.api import algorithms as _algorithms  # noqa: F401  (registers
                                                 # the built-in drivers)
from repro.coresets import algorithms as _coreset_algorithms  # noqa: F401
                                                 # (registers coreset_kmeans)
from repro import robust as _robust  # noqa: F401  (registers kzmeans)

__all__ = [
    "Backend", "ClusterResult", "MeshBackend",
    "VirtualBackend", "fit", "fit_update", "get_algorithm",
    "list_algorithms",
    "register_algorithm", "resolve_backend", "uplink_bytes",
]

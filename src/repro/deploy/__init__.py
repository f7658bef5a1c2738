"""Whole-model CIM deployment engine.

Fuses MDM planning across every layer of a model into a constant number
of device programs (``planner``), persists per-layer plans in a
content-addressed cache (``cache``), and packages model parameters into
the stacked :class:`CimDeployment` trees the serving path consumes
(``engine``).  ``ServeEngine`` calls :func:`deploy_model_params` at
init when ``cfg.cim.enabled`` is set; ``benchmarks/deploy_throughput``
records the fused-vs-per-layer planning and cache-hit redeploy wins.
"""
from repro.deploy.cache import (  # noqa: F401
    PLAN_CACHE_VERSION,
    CacheStats,
    PlanCache,
    manifest_key,
    plan_key,
    weight_fingerprint,
)
from repro.deploy.lifetime import (  # noqa: F401
    DEMOTED_RUNTIME,
    MatrixLifetime,
    pad_host_deployment,
    restack_group,
)
from repro.deploy.engine import (  # noqa: F401
    DEPLOYABLE,
    MOE_EXPERT_NAMES,
    collect_model_matrices,
    collect_projection_matrices,
    deploy_matrices,
    deploy_model_params,
    package_deployment_host,
    spec_from_config,
)
from repro.deploy.planner import (  # noqa: F401
    fingerprint_matrices,
    plan_matrices,
    plan_model_tiles,
)

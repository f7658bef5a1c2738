from repro.kernels.cim_mvm.ops import (  # noqa: F401
    CimDeployment,
    cim_mvm,
    deploy,
    resolve_impl,
    served_impl,
)

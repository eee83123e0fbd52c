from .cache import enable_compile_cache
from .mesh import (make_production_mesh, make_host_mesh, mesh_devices,
                   PEAK_FLOPS_BF16, HBM_BW, ICI_BW)

__all__ = ["enable_compile_cache", "make_production_mesh", "make_host_mesh",
           "mesh_devices", "PEAK_FLOPS_BF16", "HBM_BW", "ICI_BW"]

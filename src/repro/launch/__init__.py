# Launch layer: the device mesh and the train/serve drivers.
from .mesh import make_local_mesh

__all__ = ["make_local_mesh"]

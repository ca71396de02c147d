from .mesh import make_mesh, pick_mesh_shape
from .spmd import make_step, stack_states

__all__ = [
    "make_mesh",
    "pick_mesh_shape",
    "make_step",
    "stack_states",
]

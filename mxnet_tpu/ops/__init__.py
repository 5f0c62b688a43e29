"""Operator library: single registry, pure-JAX implementations.

Importing this package registers the full op surface (reference:
src/operator/ — SURVEY.md §2.2).  Submodules group ops the way the reference
tree does.
"""
from . import registry
from .registry import get, find, register, alias, list_ops, op_count, OpDef

# registration side effects
from . import elemwise      # noqa: F401
from . import reduce        # noqa: F401
from . import matrix        # noqa: F401
from . import indexing      # noqa: F401
from . import init_ops      # noqa: F401
from . import nn            # noqa: F401
from . import sampling      # noqa: F401
from . import sequence      # noqa: F401
from . import attention     # noqa: F401
from . import custom        # noqa: F401
from . import detection     # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn           # noqa: F401
from . import linalg        # noqa: F401
from . import moe           # noqa: F401
from . import spatial       # noqa: F401
from . import contrib_ops   # noqa: F401
from . import chunked_loss  # noqa: F401
from . import control_flow  # noqa: F401

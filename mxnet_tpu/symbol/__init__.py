"""Symbolic API (``mx.sym`` / ``mx.symbol``)."""
from .symbol import (Symbol, Node, Variable, var, Group, load, load_json,
                     zeros, ones, arange)
from .register import init_symbol_module

init_symbol_module(globals())


from .control_flow import foreach as _contrib_foreach   # sym.contrib.foreach

from ..base import ContribNamespace as _ContribNS
contrib = _ContribNS(globals())

from . import random    # noqa: E402  mx.sym.random.*
from . import linalg    # noqa: E402  mx.sym.linalg.*

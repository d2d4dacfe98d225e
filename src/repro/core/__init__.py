"""repro.core — the paper's contribution: DGS + SAMomentum + async runtime."""
from . import (async_sim, baselines, distributed, engine, paramspace,
               samomentum, scan_runner, server, sparsify)
from .baselines import ASGD, DGS, DGCAsync, DGSPlain, GDAsync, make_strategy
from .distributed import ExchangeConfig, ExchangeState, exchange, init_state
from .engine import (CompressionSpec, SelectionEngine, get_engine,
                     register_engine, resolve_engine)
from .paramspace import ParamSpace
from .samomentum import SAMomentumState
from .scan_runner import run_async_scan
from .sparsify import (SparseLeaf, density_to_k, quantize_dequantize,
                       topk_select)

__all__ = [
    "async_sim", "baselines", "distributed", "engine", "paramspace",
    "samomentum", "server", "sparsify", "ASGD", "DGS", "DGCAsync",
    "DGSPlain", "GDAsync", "make_strategy", "ExchangeConfig",
    "ExchangeState", "exchange", "init_state", "CompressionSpec",
    "SelectionEngine", "get_engine", "register_engine", "resolve_engine",
    "ParamSpace", "SAMomentumState", "SparseLeaf", "density_to_k",
    "topk_select",
]

from . import mesh, roofline, sharding, steps

__all__ = ["cluster", "mesh", "roofline", "sharding", "steps"]


def __getattr__(name):
    # lazy: the cluster CLI pulls in repro.cluster, which most launch users
    # (mesh/serve paths) never need
    if name == "cluster":
        import importlib

        return importlib.import_module(".cluster", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

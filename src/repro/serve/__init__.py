"""LM serving engine and KV-cache planner.

Both load lazily, so importing the package does not pay for — or
inherit the failure surface of — the transformer stack.
"""
_ENGINE = {"Completed", "Engine", "Request"}
_PLANNER = {"KVPlan", "plan_kv"}

__all__ = sorted(_ENGINE | _PLANNER)


def __getattr__(name):
    if name in _ENGINE:
        from . import engine
        return getattr(engine, name)
    if name in _PLANNER:
        from . import kv_planner
        return getattr(kv_planner, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

"""Model registry.

The reference discovers model classes by reflection over the models
package (utils.py:114-118: every public CamelCase name). Here models
register explicitly; ``model_names()`` feeds the ``--model`` choices.
"""

from __future__ import annotations

_REGISTRY = {}


def register_model(name: str):
    def deco(cls):
        _REGISTRY[name] = cls
        return cls
    return deco


def get_model(name: str):
    _ensure_loaded()
    return _REGISTRY[name]


def model_names():
    _ensure_loaded()
    return sorted(_REGISTRY)


_loaded = False


def _ensure_loaded():
    global _loaded
    if _loaded:
        return
    _loaded = True
    # import for registration side effects; keep lazy so `ops`-only
    # users never pay for flax imports
    import importlib
    import importlib.util
    for mod in ("resnet9", "fixup_resnet9", "resnet18", "resnets", "gpt2",
                "joyai", "nemotron_h", "granite_hybrid", "smallthinker",
                "ouro"):
        name = f"commefficient_tpu.models.{mod}"
        # skip modules not yet written, but let real import errors
        # inside existing ones propagate
        if importlib.util.find_spec(name) is not None:
            importlib.import_module(name)

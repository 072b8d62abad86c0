"""Model tier — JAX functional model definitions for the BASELINE architectures.

The reference has no in-repo model code (SURVEY §0: inference is delegated to
external providers); this tier is the real implementation of what model-registry's
PRD only specifies (managed local models, safetensors format, architectures —
modules/model-registry/docs/PRD.md:200-224).
"""

import importlib
from types import ModuleType

from .configs import MODEL_CONFIGS, ModelConfig, get_config

__all__ = ["MODEL_CONFIGS", "ModelConfig", "get_config", "decoder_module"]

#: decoder architectures the serving scheduler drives → their model module.
#: Each exposes init_params, forward_paged_decode, forward_paged_mixed,
#: lm_head_logits and gather_last_hidden; one with recurrent state also
#: init_state.
_DECODERS = {"llama": "llama", "falcon_h1": "falcon_h1",
             "sdar_moe": "sdar_moe", "kimi_k2": "kimi_k2",
             "granite_hybrid": "granite_hybrid", "nemotron_h": "nemotron_h",
             "solar_open2": "solar_open2", "motif": "motif",
             "ouro": "ouro", "laguna": "laguna",
             "glm_moe_dsa": "glm_dsa"}


def decoder_module(cfg: ModelConfig) -> ModuleType:
    """The model module of a decoder ``ModelConfig.architecture``."""
    try:
        name = _DECODERS[cfg.architecture]
    except KeyError:
        raise ValueError(
            f"{cfg.name}: architecture {cfg.architecture!r} is not a decoder "
            f"the scheduler serves (known: {sorted(_DECODERS)})") from None
    return importlib.import_module(f"{__name__}.{name}")

import importlib

from ..config import MODEL_CONFIG_TYPES
from .distilbert import (  # noqa: F401
    DDoSClassifier,
    DistilBertEncoder,
    init_params,
    param_count,
)
from .hf_convert import flax_to_hf, hf_to_flax  # noqa: F401
from .presets import PRESETS, model_preset, preset_names  # noqa: F401


def family_module(cfg):
    """The module ``models/<family>.py`` of the family that registered
    ``cfg``'s type (``config.MODEL_CONFIG_TYPES``), imported when first
    asked for: it holds the family's ``Classifier`` and ``forward_flops``.
    None for a ``ModelConfig`` (the BERT encoder of ``distilbert.py``)."""
    for family, config_type in MODEL_CONFIG_TYPES.items():
        if isinstance(cfg, config_type):
            return importlib.import_module(f".{family}", __name__)
    return None


def build_classifier(cfg):
    """The classifier module for a model configuration object: THE place a
    model class is chosen (engine, federated steps, distillation, scorer,
    profiler and CLI all come here), by the configuration's type."""
    module = family_module(cfg)
    return DDoSClassifier(cfg) if module is None else module.Classifier(cfg)

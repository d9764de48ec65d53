"""JAX/TPU model zoo for the in-process server (flagship models).

``model_sets("builtin,jax,resnet,language,pipeline")`` is the single set-name
resolver used by the serve and perf CLIs; ``jax_models()`` is the small-CNN
vision set (``--models jax``), ``resnet_models()`` the resnet50 of BASELINE
config 3, ``language_models()`` the tokenizer→streaming-LM stack of BASELINE
config 5, and ``pipeline_models()`` the full-size vision ensemble DAG
(preprocess → resnet50 backbone → classification postprocess).
"""

from client_tpu.utils import InferenceServerException


def jax_models():
    from client_tpu.serve.models.vision import cnn_classifier_model
    return [cnn_classifier_model()]


def resnet_models():
    from client_tpu.serve.models.vision import resnet50_model
    return [resnet50_model()]


def language_models(speculative=None):
    from client_tpu.serve.models.language import language_models as _lm
    return _lm(speculative=speculative)


def pipeline_models(warmup=False):
    """Full-size vision pipeline (224px resnet50 backbone): the ensemble
    DAG acceptance workload at serving scale."""
    from client_tpu.serve.models.vision import (
        _RESNET50_STAGES,
        vision_pipeline_models,
    )

    return vision_pipeline_models(
        image_size=224, stages=_RESNET50_STAGES, num_classes=1000,
        max_batch_size=64, warmup=warmup,
    )


def model_sets(names, speculative=None):
    """Resolve a comma-separated set list
    (builtin,jax,resnet,language,pipeline).  ``speculative`` (a
    SpecConfig-shaped dict) applies to the ``language`` set's batched
    engines only — perf's ``--speculative K --drafter ngram`` threads
    through here."""
    from client_tpu.serve.builtins import default_models

    loaders = {
        "builtin": default_models,
        "jax": jax_models,
        "resnet": resnet_models,
        "language": lambda: language_models(speculative=speculative),
        "pipeline": pipeline_models,
    }
    models = []
    for name in names.split(","):
        name = name.strip()
        if not name:
            continue
        if name not in loaders:
            raise InferenceServerException(
                f"unknown model set '{name}' (available: "
                f"{', '.join(sorted(loaders))})"
            )
        models.extend(loaders[name]())
    return models

from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.hf.auto import auto_model_for_config, from_pretrained  # noqa: F401
from perceiver_io_tpu.hf.convert import (  # noqa: F401
    convert_image_classifier,
    convert_image_classifier_config,
    convert_masked_language_model,
    convert_mlm_config,
    convert_optical_flow,
    convert_optical_flow_config,
)
from perceiver_io_tpu.hf.lightning_ckpt import (  # noqa: F401
    export_causal_sequence_model_state_dict,
    import_clm_checkpoint,
    import_image_classifier_checkpoint,
    import_mlm_checkpoint,
    import_symbolic_audio_checkpoint,
    import_timeseries_checkpoint,
    import_text_classifier_checkpoint,
    load_lightning_checkpoint,
    save_lightning_checkpoint,
)
from perceiver_io_tpu.hf.mask_filler import MaskFiller  # noqa: F401
from perceiver_io_tpu.hf.pipelines import (  # noqa: F401
    FillMaskPipeline,
    ImageClassificationPipeline,
    OpticalFlowPipeline,
    SymbolicAudioGenerationPipeline,
    TextClassificationPipeline,
    TextGenerationPipeline,
    pipeline,
)

__all__ = [
    "auto_model_for_config",
    "from_pretrained",
    "convert_image_classifier",
    "convert_image_classifier_config",
    "convert_masked_language_model",
    "convert_mlm_config",
    "convert_optical_flow",
    "convert_optical_flow_config",
    "export_causal_sequence_model_state_dict",
    "import_clm_checkpoint",
    "import_image_classifier_checkpoint",
    "import_mlm_checkpoint",
    "import_symbolic_audio_checkpoint",
    "import_timeseries_checkpoint",
    "import_text_classifier_checkpoint",
    "load_lightning_checkpoint",
    "save_lightning_checkpoint",
    "MaskFiller",
    "FillMaskPipeline",
    "ImageClassificationPipeline",
    "OpticalFlowPipeline",
    "SymbolicAudioGenerationPipeline",
    "TextClassificationPipeline",
    "TextGenerationPipeline",
    "pipeline",
]

_STARTUP.close(_IMPORTING)

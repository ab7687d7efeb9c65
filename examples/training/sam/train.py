"""Programmatic Perceiver AR symbolic-audio training on MaestroV3 — the
library-as-toolkit variant of train.sh (reference:
examples/training/sam/maestrov3/train.py:1-50): build the datamodule, model
config and trainer directly instead of going through the auto-CLI.

Expects the MaestroV3 MIDI archive (``maestro-v3.0.0-midi.zip``) under
``data_args.dataset_dir`` — ``MaestroV3DataModule.prepare_data`` extracts it,
splits by the bundled metadata json, and encodes to the flat token memmap.

Run from the repo root: ``PYTHONPATH=. python examples/training/sam/train.py``
"""

from __future__ import annotations

import numpy as np

from perceiver_io_tpu.data.audio.symbolic import MaestroV3DataModule
from perceiver_io_tpu.models.audio.symbolic import SymbolicAudioModel, SymbolicAudioModelConfig
from perceiver_io_tpu.scripts import cli
from perceiver_io_tpu.training.losses import clm_loss_fn

MAX_SEQ_LEN = 6144

data_args = dict(
    dataset_dir=".cache/maestro",
    max_seq_len=MAX_SEQ_LEN,
    batch_size=16,
    preproc_workers=4,
)

trainer_args = cli.TrainerArgs(
    strategy="dp",
    precision="bf16",
    gradient_clip_val=1.0,
    max_steps=100_000,
    val_interval=1000,
    name="sam_maestro",
)

opt_args = cli.OptimizerArgs(lr=2e-4, lr_scheduler="cosine_with_warmup", warmup_steps=200)


def main():
    data = MaestroV3DataModule(**data_args)
    data.prepare_data()
    # paper presets (reference: scripts/audio/symbolic.py:14-28)
    config = SymbolicAudioModelConfig(
        vocab_size=data.vocab_size,
        max_seq_len=MAX_SEQ_LEN,
        max_latents=1024,
        num_channels=512,
        num_self_attention_layers=8,
        cross_attention_dropout=0.5,
    )
    model = SymbolicAudioModel(config, dtype=cli.activation_dtype(trainer_args))

    init_batch = {
        "x": np.zeros((1, MAX_SEQ_LEN), np.int32),
        "prefix_len": MAX_SEQ_LEN - config.max_latents,
        "pad_mask": np.zeros((1, MAX_SEQ_LEN), bool),
    }
    cli.run_training(
        model,
        config,
        lambda apply_fn: clm_loss_fn(apply_fn, config.max_latents),
        init_batch,
        cli.cycle(data.train_batches()),
        data.valid_batches(),
        trainer_args,
        opt_args,
    )


if __name__ == "__main__":
    main()

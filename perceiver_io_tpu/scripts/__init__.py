"""L5 CLI layer — auto-CLI entry points over the config dataclasses
(reference: perceiver/scripts/*, SURVEY §2.6).

Each task module exposes ``main(argv)`` and is runnable as
``python -m perceiver_io_tpu.scripts.<domain>.<task> fit --model.* --data.*``.
"""

from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

_STARTUP.close(_IMPORTING)

"""CLI entry: `python -m lightgbm_tpu task=train config=train.conf ...`
(the reference's `lightgbm` binary, src/main.cpp)."""

from .application import main

main()

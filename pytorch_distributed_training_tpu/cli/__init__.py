"""CLI (L7 in SURVEY.md §1): the user-facing entrypoint, ``cli.main``.

Flag-compatible with the reference's 7 click options (src/main.py:18-25):
``--data-dir --distributed --use-cpu --batch-size --num-workers
--learning-rate --weight-decay``, extended with the knobs the BASELINE.json
configs require (model/dataset selection, precision, grad accumulation, mesh
axes, epochs, checkpointing).

``main`` is not imported here: ``python -m …cli.main`` would then find the
module already in ``sys.modules`` and runpy warns on every start.
"""

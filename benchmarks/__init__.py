"""The repo's benchmark: ``python3 benchmarks/run.py`` runs one cell once.

Everything is found by the names in ``BENCHMARK.json`` (see PERF.md):

- ``configs/<config>.json``: the subject model's published keys, what was
  ``reduced`` and ``assumed``, the ``crosscoder`` (and ``serve``) fields, and
  ``arch``: the architecture of its subject LM (absent: ``gemma2_block``);
- ``arch/<arch>.py``: what the harness knows about that LM's block: the
  file's keys to ``lm.LMConfig``, its plain reference with the limit the
  harvest is held to, its forward FLOPs (see ``arch/__init__.py``);
- ``traffic/<traffic>.json``: the ``runner`` and ``generator`` it uses and
  their parameters;
- ``metrics/<metric>.json``: one per-layer metric: its manifest entry (less
  the cells, which only ``BENCHMARK.json`` lists), the ``reducer`` that reads
  it and the reducer's ``args``; ``"chip_only": true`` where only a chip
  can give it (a kernel's own ops), so that a traced run on the CPU may lack it;
- ``runners/``, ``generators/``, ``reducers/``: one module per kind, found by
  name, so a new kind is a new file;
- ``reference/``: plain float32 references; ``shapes.py``: needed operations
  and bytes; ``peaks.json``; ``attribution/*.json``: XLA module -> layer,
  joined in the order of their names;
  ``trace_reduce.py``: trace -> busy/idle, device time per layer, breakdown;
- ``tools/``: back-to-back series of runs and the two sets of six.

A later PR adds a configuration, a traffic mix, a cell, a per-layer metric,
attribution rules or an architecture by adding files and manifest entries,
never by editing a file that is here. A configuration of another published
model also adds that model's numbers under ``tests/benchmarks/published/``.
"""

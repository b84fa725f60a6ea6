"""Chip benchmark of BlobSeer as the checkpoint store of a training job.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it finds and prints
one JSON result line.  Configurations (``configs/``), traffic mixes
(``traffic/``) and per-layer metric readers (``metrics/``) are files of
their own, found by the names in ``BENCHMARK.json``; a configuration's
architecture is a module of ``families/``, found by its ``model_type``.
"""

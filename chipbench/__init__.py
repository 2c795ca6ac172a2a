"""Chip benchmark of the SpKAdd engine, driven by ``BENCHMARK.json``.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the accelerator the process is started on.
Everything a cell needs is found by name: its configuration in
``configs/<config>.json``, its traffic in ``traffic/<traffic>.json``, the
driver and generator that configuration names in ``drivers/`` and ``gen/``,
and each metric's reader in ``e2e/`` or ``layers/``.
"""

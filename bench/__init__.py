"""The chip benchmark of the sparse pattern search service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the accelerator it is started
on and prints one JSON result line. Everything a cell is made of sits in
files of its own, found by name: ``configs/<config>.json`` (a deployment),
``traffic/<mix>.json`` (a traffic mix), ``generators/<name>.py`` (the data
a configuration names), ``references/<name>.py`` (its plain reference) and
``metrics/<metric>.py`` (a per-layer reader).
"""

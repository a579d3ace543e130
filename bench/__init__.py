"""On-chip benchmark of the serving path (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the TPU it is started on and
prints one JSON result line.  Everything a cell needs is found by name:
``configs/<config>.json`` (model and engine settings),
``traffic/<mix>.json`` (the mix's parameters, read by
:mod:`bench.traffic`), ``metrics/<metric>.py`` (one reader per
per-layer metric), ``references/<reference>.py`` (the plain float32
model that decides ``correct``) and ``archs/<model_type>.py`` (the
mapping onto the program, the counts and the CPU cell's sizes of one
architecture).
"""

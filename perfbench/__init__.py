"""The benchmark: one command (run.py) over cells named in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix, one
reference or one per-layer metric is a file of its own, found by name:
configs/<config>.json, traffic/<traffic>.json, kinds/<kind>.py (the
generator a traffic file names), references/<name>.py and
arith/<name>.py (named by the configuration file), metrics/<metric>.py.
A later PR adds cells by adding such files and entries to BENCHMARK.json.
"""

"""Spec, data and compile-cache helpers shared by chip_smoke.py and
tests/test_chip_compile.py (see README.md in this directory). The
benchmark itself is perfbench/.
"""

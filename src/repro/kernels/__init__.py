# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# This module stays pallas-free: IMPLS lives here so
# CLI flag definitions can name the backends without importing
# pallas-tpu; resolution/dispatch is repro.kernels.ops.
IMPLS = ("auto", "pallas", "pallas_interpret", "ref")

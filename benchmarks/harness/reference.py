"""The plain reference of the block the benchmark began with lives with
its family (`benchmarks/families/dense.py`): these are its names, kept for
what imports them. Each family brings its own reference."""

from benchmarks.harness import manifest

_dense = manifest.load_family(manifest.DEFAULT_FAMILY)
NORM_EPS = _dense.NORM_EPS
logits_one_sequence = _dense.logits_one_sequence
make_logits_fn = _dense.make_logits_fn
make_row_nll_fn = _dense.make_row_nll_fn
lm_loss = _dense.lm_loss

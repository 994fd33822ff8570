#!/usr/bin/env bash
# Standalone interpret-mode kernel parity suite: every Pallas kernel's
# CPU oracle tests (topk / sparsify / quant / sparse_grad + row_gather /
# batchtopk / paged_attention),
# without the full tier-1 run — so a kernel regression is catchable in
# ~a minute while iterating on ops/. Same pytest flags as tier1.sh so
# the two gates can never diverge on collection behavior.
# Run from anywhere; executes at the repo root. Extra args pass through
# (e.g. scripts/kernels.sh -k duplicate -x).
cd "$(dirname "$0")/.." || exit 1
exec env JAX_PLATFORMS=cpu python -m pytest -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
  -p no:randomly \
  tests/test_topk_pallas.py \
  tests/test_factored_decode.py \
  tests/test_quant.py \
  tests/test_sparse_grad.py \
  tests/test_row_gather.py \
  tests/test_batchtopk_pallas.py \
  tests/test_paged_attention.py \
  tests/test_dispatch.py \
  "$@"

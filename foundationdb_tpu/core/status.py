"""Per-transaction resolution statuses — single source of truth.

Ref: ConflictBatch::TransactionCommitted / TransactionConflict /
TransactionTooOld in fdbserver/SkipList.cpp.
"""

COMMITTED = 0
CONFLICT = 1
TOO_OLD = 2

# Device-side only: a CONFLICT that only a coarse summary raised (the
# exact lanes found nothing). ops/conflict.py's full steps return it so
# that one status array still carries everything a dispatch reads back;
# the resolver counts it (conflicts_coarse_only) and answers CONFLICT.
CONFLICT_COARSE = 3

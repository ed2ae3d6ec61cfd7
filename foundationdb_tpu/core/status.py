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

# Device-side only: a CONFLICT that only an earlier accepted transaction
# of the same batch raised (admissible against the history and the
# window, refused at the intra-batch fixpoint). Every step returns it;
# the resolver counts it (conflicts_in_batch) and answers CONFLICT.
# Both device-side codes lie above TOO_OLD, and the host folds every
# code above TOO_OLD to CONFLICT.
CONFLICT_BATCH = 4

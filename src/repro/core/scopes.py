"""Names of the device scopes (``jax.named_scope``) of the serving programs.

Each part of the engine's device work runs under one of these names, so a
profiler trace can split a program's time into its parts: the name lands in
every HLO operation's ``op_name`` metadata (``.../draft/...``).  The strings
are the contract with the trace reduction that reads them, so they stay
fixed however the code around them moves.  Scopes nest (the draft feeds
commit their shallow caches inside ``draft``); an operation belongs to the
innermost scope on its path.
"""

DRAFT = "draft"                  # the K+1 shallow feeds and the draft head
VERIFY = "verify"                # deep pass, verifier logits, accept/reject
COMMIT = "commit"                # cache and token commit, superstep bookkeeping
LEARN_LOG = "learn.log"          # replay-buffer logging of block tuples
LEARN_UPDATE = "learn.update"    # one drafter optimizer step
PREFILL_CHUNK = "prefill.chunk"  # one batched prefill-chunk step
PREFILL_ADMIT = "prefill.admit"  # the admission programs

ALL = (DRAFT, VERIFY, COMMIT, LEARN_LOG, LEARN_UPDATE, PREFILL_CHUNK,
       PREFILL_ADMIT)

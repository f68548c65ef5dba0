"""Speculative decoding for the serving engine: n-gram drafting + fused
multi-step decode (ISSUE 9).

The PR 2 mixed-mode ragged paged-attention kernel already scores T>1
query tokens per sequence under a causal mask, so *verifying K draft
tokens is the same program shape as a prefill chunk*: one dispatch runs
the whole transformer over ``[B, K]`` query tokens against the paged KV
history, emitting logits at every position.  That single observation
buys two decode accelerations without touching the kernel contract:

- **``fused`` mode (self-draft)**: K sequential T=1 decode steps are
  unrolled inside ONE jitted program, so the host pays one dispatch per
  K tokens instead of per token.  This is the degenerate speculation
  case (every "draft" is the model's own sample, acceptance is 1.0 by
  construction) and wins whenever host->device dispatch latency is
  nontrivial — which the CPU bench already shows for tiny step times.
- **``ngram`` mode (prompt-lookup speculation)**: a drafter proposes
  K-1 tokens by matching the sequence's recent n-gram context against
  its own prompt+output history, and the engine verifies all of them in
  ONE mixed-mode dispatch at the T=K bucket.  Acceptance is the classic
  longest-accepted-prefix rule — draft j is accepted iff it equals the
  verifier's own token for position j-1 — computed ON DEVICE, so a spec
  step commits between 1 (all drafts rejected: the verifier's first
  token is still a real token) and K tokens with zero host involvement.

**Division of labor (the JL002 contract)**: the host owns the *history
table* — a per-slot ``[max_seq_len]`` token array holding the prompt
plus every RETIRED (drained) output token — and rebuilds/uploads it only
at admission and at the engine's existing drain points.  The *matching*
runs on device inside the verify step (:func:`lookup_drafts`), against a
device-resident ``recent`` ring of the last ``ngram_max`` committed
tokens that the step itself maintains (:func:`shift_append`).  Warm spec
steps therefore issue zero extra host<->device syncs and zero per-step
host reads — the steady-state loop is dispatch-only, exactly like the
plain engine.

**KV rollback**: the verify step writes KV rows for all K positions
before acceptance is known.  Rejected positions simply do not advance
``positions`` — the ragged kernel masks reads by ``context_lens``, so
stale rows are unreachable and are overwritten in place when the cursor
eventually crosses them.  Writes can never land in a shared page: prefix
sharing is page-aligned over FULL prompt pages and the fully-cached case
privatizes its last page copy-on-write before the first decode write
(see ``prefix_cache.py``), so draft writes only ever touch pages the
sequence owns exclusively.  Host-side block-table overshoot (pages grown
for tokens that were then rejected) is rolled back at drain time via
``PageAllocator.truncate`` — refcount-aware, so a shared page can only
lose this sequence's reference, never a sibling's.

**Correctness contract**: greedy spec-on outputs (both modes) bit-match
the spec-off oracle — acceptance compares the verifier's own argmax, so
every committed token is exactly the token sequential greedy decoding
would have produced.  Sampled configs draw one independent key per
position; the accept-iff-equal rule preserves the sequential sampling
distribution token-for-token, but the key *stream* differs from the
sequential engine's, so sampled outputs are distribution-correct rather
than bit-identical.

Everything here is off by default (``FLAGS_spec_decode=""``); the plain
engine path is untouched and bit-identical to PR 8.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags

# Three distinct pad values so padding can never produce a false n-gram
# match: history rows pad with HIST_PAD past their length, the recent
# ring pads with CTX_PAD before enough tokens committed, and the shifted
# history views pad with _SHIFT_PAD at the left edge.  Real vocab ids
# are >= 0, so no pad equals a token and no pad equals another pad.
HIST_PAD = np.int32(-1)
CTX_PAD = np.int32(-2)
_SHIFT_PAD = np.int32(-3)

MODES = ("ngram", "fused")


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Resolved speculative-decoding configuration (static per engine:
    the verify/fused programs are jitted per (sampling config, k))."""

    mode: str          # "ngram" | "fused"
    k: int             # tokens per speculative dispatch (the T=k bucket)
    ngram_max: int     # longest drafter context (ngram mode only)


def resolve_spec_config(spec_decode=None, k: Optional[int] = None,
                        ngram_max: Optional[int] = None
                        ) -> Optional[SpecConfig]:
    """Engine-kwarg/flag resolution: ``None`` defers to ``FLAGS_spec_decode``
    ('' = off), ``True`` means 'ngram', ``False`` forces off."""
    mode = spec_decode
    if mode is None:
        mode = flags.flag("spec_decode")
    if mode is True:
        mode = "ngram"
    if not mode:
        return None
    if mode not in MODES:
        raise ValueError(
            f"spec_decode must be one of {MODES} (or ''/False for off), "
            f"got {mode!r}")
    k = int(k if k is not None else flags.flag("spec_k"))
    if k < 2:
        raise ValueError(f"spec_k must be >= 2 (got {k}); K=1 is just the "
                         "plain decode step")
    n = int(ngram_max if ngram_max is not None
            else flags.flag("spec_ngram_max"))
    return SpecConfig(mode, k, max(1, n))


# ---------------------------------------------------------------------------
# device-side drafter (traced inside the engine's verify step)
# ---------------------------------------------------------------------------

def lookup_drafts(hist, hist_len, recent, k: int, nmax: int):
    """Prompt-lookup draft proposal, fully on device.

    For every candidate position ``p`` of each row's history the drafter
    scores the longest suffix of ``recent`` (the last ``nmax`` committed
    tokens, right-aligned) that matches ``hist[p-L:p]``; the winner is
    the longest match, most recent occurrence on ties, and the draft is
    the continuation ``hist[p : p+k-1]``.

    Args:
      hist:     [B, S] int32 — prompt + retired output tokens, padded
                with ``HIST_PAD`` past ``hist_len`` (host-rebuilt at
                drain time only).
      hist_len: [B] int32 valid tokens per row.
      recent:   [B, nmax] int32 — the device-resident ring of the last
                committed tokens (``CTX_PAD``-filled on the left).
      k, nmax:  static ints (the T=k bucket / drafter context cap).

    Returns:
      (drafts [B, k-1] int32, draft_len [B] int32) — rows with no match
      get draft_len 0 and ride the verify step as plain decode rows.
    """
    B, S = hist.shape
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    match_len = jnp.zeros((B, S), jnp.int32)
    run = jnp.ones((B, S), bool)
    for i in range(1, nmax + 1):
        # shifted[b, p] = hist[b, p-i]  (left edge -> _SHIFT_PAD)
        shifted = jnp.concatenate(
            [jnp.full((B, i), _SHIFT_PAD, hist.dtype), hist[:, :S - i]],
            axis=1)
        run = jnp.logical_and(run, shifted == recent[:, nmax - i][:, None])
        match_len = match_len + run.astype(jnp.int32)
    valid = jnp.logical_and(pos < hist_len[:, None], match_len > 0)
    score = jnp.where(valid, match_len * jnp.int32(S) + pos, jnp.int32(-1))
    best = jnp.argmax(score, axis=1).astype(jnp.int32)
    found = jnp.max(score, axis=1) >= 0
    draft_len = jnp.where(
        found,
        jnp.minimum(jnp.int32(k - 1), hist_len.astype(jnp.int32) - best),
        jnp.int32(0)).astype(jnp.int32)
    idx = jnp.minimum(best[:, None] + jnp.arange(k - 1, dtype=jnp.int32),
                      jnp.int32(S - 1))
    drafts = jnp.take_along_axis(hist, idx, axis=1)
    return drafts, draft_len


def shift_append(recent, out_tokens, n_commit):
    """Slide each row's recent ring forward by its committed count:
    ``recent[b]`` becomes the last ``nmax`` tokens of
    ``recent[b] ++ out_tokens[b, :n_commit[b]]`` (n_commit 0 = no-op)."""
    nmax = recent.shape[1]
    cat = jnp.concatenate([recent, out_tokens.astype(recent.dtype)], axis=1)
    idx = n_commit[:, None].astype(jnp.int32) + \
        jnp.arange(nmax, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(cat, idx, axis=1)


def accept_length(tokens, sampled, q_lens):
    """Longest-accepted-prefix rule.

    ``tokens``:  [B, K] — col 0 is the row's last committed token, cols
                 1.. are the draft proposals.
    ``sampled``: [B, K] — the verifier's own token for each position
                 (argmax for greedy; per-position samples otherwise).
    ``q_lens``:  [B] — 1 + draft_len (0 = inert row).

    Draft j (input col j) is accepted iff it equals ``sampled[:, j-1]``
    — i.e. the token the model itself emits after consuming everything
    before it.  Returns the COMMIT count per row: accepted drafts plus
    the one bonus token from the first unaccepted position (so an active
    row always commits >= 1), 0 for inert rows.
    """
    B, K = tokens.shape
    if K > 1:
        j = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        match = jnp.logical_and(tokens[:, 1:] == sampled[:, :-1],
                                j < (q_lens[:, None] - 1))
        a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                    axis=1).astype(jnp.int32)
    else:
        a = jnp.zeros((B,), jnp.int32)
    return jnp.where(q_lens > 0, a + 1, 0).astype(jnp.int32)


def eos_clamp(sampled, n_commit, eos_id: int):
    """Cut each row's commit count at its first committed EOS (kept,
    inclusive — sequential decoding also emits the EOS token).  Returns
    (clamped n_commit, hit_eos [B] bool)."""
    B, K = sampled.shape
    j = jnp.arange(K, dtype=jnp.int32)[None, :]
    is_eos = jnp.logical_and(sampled == jnp.int32(eos_id),
                             j < n_commit[:, None])
    first = jnp.min(jnp.where(is_eos, j, jnp.int32(K)), axis=1)
    hit = first < n_commit
    return jnp.where(hit, first + 1, n_commit).astype(jnp.int32), hit


# ---------------------------------------------------------------------------
# host-side history table (rebuilt at drain time only — never per step)
# ---------------------------------------------------------------------------

def recent_window(tokens: Sequence[int], nmax: int) -> np.ndarray:
    """Right-aligned ``[nmax]`` int32 tail of ``tokens`` (CTX_PAD fill) —
    the admission-time seed of a row's device recent ring."""
    out = np.full((nmax,), int(CTX_PAD), np.int32)
    tail = list(tokens)[-nmax:]
    if tail:
        out[nmax - len(tail):] = np.asarray(tail, np.int32)
    return out


class SpecHistory:
    """The drafter's n-gram table: per-slot prompt+output token history.

    Host-owned numpy mirror + lazily refreshed device copy: ``reset_row``
    runs at admission, ``extend_row`` when the engine gathers a step, with
    the tokens that step committed, and ``device_arrays`` re-uploads ONLY
    when a row changed (an async host->device transfer, not a sync).  The
    upload is of a COPY: steps in flight still read the array they were
    handed, and ``jnp.asarray`` of a numpy array may share its memory (on
    the CPU it often does), so the mirror must not change under them.
    """

    def __init__(self, max_batch: int, max_seq_len: int):
        self._np = np.full((max_batch, max_seq_len), int(HIST_PAD), np.int32)
        self._len = np.zeros((max_batch,), np.int32)
        self._dirty = True
        self._dev: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None

    def reset_row(self, b: int, tokens: Sequence[int]) -> None:
        """Seed slot ``b`` with a freshly admitted prompt."""
        row = self._np[b]
        row[:] = int(HIST_PAD)
        n = min(len(tokens), row.shape[0])
        if n:
            row[:n] = np.asarray(list(tokens)[:n], np.int32)
        self._len[b] = n
        self._dirty = True

    def extend_row(self, b: int, tokens: Sequence[int]) -> None:
        """Append gathered output tokens to slot ``b``'s history."""
        if not len(tokens):
            return
        row = self._np[b]
        n = int(self._len[b])
        m = min(len(tokens), row.shape[0] - n)
        if m > 0:
            row[n:n + m] = np.asarray(list(tokens)[:m], np.int32)
            self._len[b] = n + m
            self._dirty = True

    def length(self, b: int) -> int:
        return int(self._len[b])

    def device_arrays(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(hist [B, S], hist_len [B]) on device, refreshed iff dirty."""
        if self._dirty or self._dev is None:
            self._dev = (jnp.asarray(self._np.copy()),
                         jnp.asarray(self._len.copy()))
            self._dirty = False
        return self._dev

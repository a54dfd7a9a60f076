"""Batched token sampling inside jit.

One static-shaped sampler covers all slots: per-slot temperature/top-k/top-p
vectors select behavior lane-wise (greedy lanes use argmax; sampling lanes use
temperature + nucleus/top-k restricted to a static K window — restriction to
the top-K=64 candidates is exact for top-k<=64 and a standard approximation
for pure top-p, since mass beyond the top-64 logits is negligible for LLMs).

The window (``lax.top_k`` over the whole vocabulary, its softmax, masks and
one categorical draw a lane) runs under a ``lax.cond`` and only in a dispatch
where some ACTIVE lane samples (:func:`any_sampling`: the one predicate, read
by the program from its own inputs and by the engine's
``dyn_engine_greedy_dispatches_total`` from the same host vectors). A
dispatch whose active lanes are all greedy returns argmax and its
log-probability and never issues ``TopK``: 0.84 ms of a 6.43 ms decode step
at 32 lanes x 151,936 logits on a v5e (PERF.md section 6, PR 35).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

STATIC_K = 64


def resume_seed(seed: int, resume_pos: int) -> int:
    """Deterministic per-resume-position seed fold (mid-stream failover,
    llm/resume.py). A resumed request replays its emitted tokens verbatim
    as forced prefix, but the dead worker's RNG draws at those positions
    are unreplayable — continuing from the ORIGINAL seed's key would
    re-issue draws the stream already consumed. Folding the resume
    position in gives the continuation a fresh, deterministic stream:
    the same (seed, resume_pos) always resumes identically, and
    resume_pos == 0 is the identity (an un-resumed request's key chain
    is untouched)."""
    if not resume_pos:
        return seed
    # splitmix64-style mix, stable across processes/platforms
    x = (seed ^ (resume_pos * 0x9E3779B97F4A7C15)) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return x ^ (x >> 31)


@dataclass
class SamplingState:
    """Per-slot device vectors (length = max_batch)."""

    temperature: jax.Array  # f32, 0 => greedy
    top_p: jax.Array        # f32 in (0,1], 1 => off
    top_k: jax.Array        # i32, 0 => off (capped at STATIC_K)
    key: jax.Array          # [B] typed PRNG keys (new-style jax.random.key)
    freq_pen: jax.Array     # f32, 0 => off (OpenAI frequency_penalty)
    pres_pen: jax.Array     # f32, 0 => off (OpenAI presence_penalty)

    @classmethod
    def host_init(cls, max_batch: int) -> "SamplingState":
        return cls(
            temperature=np.zeros(max_batch, np.float32),
            top_p=np.ones(max_batch, np.float32),
            top_k=np.zeros(max_batch, np.int32),
            key=jax.random.split(jax.random.key(0), max_batch),
            freq_pen=np.zeros(max_batch, np.float32),
            pres_pen=np.zeros(max_batch, np.float32),
        )


def apply_penalties(logits: jax.Array, counts: jax.Array,
                    freq_pen: jax.Array, pres_pen: jax.Array) -> jax.Array:
    """OpenAI frequency/presence penalties over GENERATED-token counts
    (completion text only, the vLLM-compatible reading): zero-penalty lanes
    are a bitwise no-op. logits [B,V] f32, counts [B,V] i32."""
    cf = counts.astype(jnp.float32)
    return (logits - freq_pen[:, None] * cf
            - pres_pen[:, None] * (cf > 0).astype(jnp.float32))


def any_sampling(temperature, active=None):
    """Does any lane that counts in this dispatch sample (temperature > 0)?
    ``active`` masks out lanes the dispatch carries but does not serve: a
    slot keeps its last request's temperature after release. Works on the
    program's traced vectors and on the host's NumPy ones alike."""
    sampling = temperature > 0.0
    if active is not None:
        sampling = sampling & active
    return sampling.any()


def _window_draw(logits: jax.Array, temperature: jax.Array,
                 top_p: jax.Array, top_k: jax.Array, sub: jax.Array
                 ) -> jax.Array:
    """One draw a lane [B] from the top-STATIC_K window: temperature, then
    top-k/top-p masks, then a categorical draw with the lane's subkey."""
    vals, idxs = jax.lax.top_k(logits, STATIC_K)  # [B,K]
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = vals / temp
    probs = jax.nn.softmax(scaled, axis=-1)
    # top-k mask (0 => off)
    karr = jnp.where(top_k[:, None] > 0, top_k[:, None], STATIC_K)
    kmask = jnp.arange(STATIC_K)[None, :] < karr
    # top-p (nucleus) mask over the sorted window: keep the smallest prefix
    # with cumulative mass >= top_p (always keep the first candidate)
    cum = jnp.cumsum(probs, axis=-1)
    pmask = (cum - probs) < top_p[:, None]
    mask = kmask & pmask
    masked = jnp.where(mask, scaled, -jnp.inf)
    draw = jax.vmap(jax.random.categorical)(sub, masked)
    return jnp.take_along_axis(idxs, draw[:, None], axis=-1)[:, 0]


def sample(logits: jax.Array, temperature: jax.Array, top_p: jax.Array,
           top_k: jax.Array, key: jax.Array,
           active: Optional[jax.Array] = None
           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """logits [B,V] f32 -> (tokens [B] i32, logprob [B] f32, new_keys [B]).

    Greedy lanes (temperature==0) take argmax; others sample within the
    top-STATIC_K window with temperature, then top-k/top-p masks. The window
    is computed, for every lane, only if :func:`any_sampling` of
    (``temperature``, ``active`` [B] bool or None = every lane counts);
    otherwise no lane's result needs it and the branch costs nothing. Keys
    advance the same way in both cases, so a seeded lane's stream does not
    depend on whether its neighbours sampled.
    """
    greedy_tok = jnp.argmax(logits, axis=-1)
    split = jax.vmap(lambda k: jax.random.split(k, 2))(key)  # [B,2] typed
    new_keys, sub = split[:, 0], split[:, 1]
    sampled_tok = jax.lax.cond(
        any_sampling(temperature, active),
        lambda: _window_draw(logits, temperature, top_p, top_k, sub),
        lambda: greedy_tok)

    token = jnp.where(temperature <= 0.0, greedy_tok, sampled_tok)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logprob = jnp.take_along_axis(logp_all, token[:, None], axis=-1)[:, 0]
    return token.astype(jnp.int32), logprob, new_keys


# ---------------------------------------------------------------------------
# speculative decoding: verify-side sampling (in-jit) + host-side acceptance
# ---------------------------------------------------------------------------
# The verify program runs one forward over T = K+1 positions per lane
# (position 0 = the last committed token; positions 1..K = draft tokens) and
# hands the host everything acceptance needs in ONE packed fetch:
#
#   greedy_tok[t]   argmax of the target distribution at position t
#   full_tok[t]     a token sampled from the full target distribution
#   resid_tok[i]    a token sampled from the RESIDUAL distribution at draft
#                   position i: the target with the draft token's mass
#                   removed, renormalized
#   p_draft[i]      target probability of draft token i (within the masked
#                   sampling window — the distribution sample() actually
#                   draws from)
#   u[i]            uniform draw for the accept test
#
# Both in-tree proposers are DETERMINISTIC (n-gram lookup; greedy draft
# model), i.e. the proposal distribution q is a point mass at the drafted
# token. Rejection sampling then reduces to: accept draft d with probability
# min(1, p(d)/q(d)) = p(d); on rejection emit a token from
# norm(max(0, p - q)) = p with d's mass removed — which preserves the target
# distribution exactly (Leviathan et al., 2023, spec-sampling lemma with a
# delta proposal). Greedy lanes skip all of that: accept iff d == argmax.


def spec_pack_width(K: int) -> int:
    """Columns in the packed verify output for draft length ``K``."""
    return 4 * (K + 1) + 5 * K


def spec_verify(logits: jax.Array, drafts: jax.Array,
                temperature: jax.Array, top_p: jax.Array, top_k: jax.Array,
                key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """In-jit verify sampling. ``logits`` [B, K+1, V] f32 (penalties already
    applied), ``drafts`` [B, K] i32. Returns (packed [B, spec_pack_width(K)]
    f32, new_keys [B]). Token ids < 2^24 are exact in f32, so one packed
    array carries ids and logprobs losslessly (same trick as decode)."""
    B, T, V = logits.shape
    K = T - 1
    greedy = jnp.argmax(logits, axis=-1)                          # [B,T]
    logp_all = jax.nn.log_softmax(logits, axis=-1)                # [B,T,V]
    logp_greedy = jnp.take_along_axis(
        logp_all, greedy[..., None], axis=-1)[..., 0]             # [B,T]

    # the masked sampling window, replicating sample() exactly: top-STATIC_K
    # candidates, temperature scaling, then top-k/top-p masks
    vals, idxs = jax.lax.top_k(logits, STATIC_K)                  # [B,T,Kw]
    temp = jnp.maximum(temperature, 1e-6)[:, None, None]
    scaled = vals / temp
    probs = jax.nn.softmax(scaled, axis=-1)
    karr = jnp.where(top_k > 0, top_k, STATIC_K)[:, None, None]
    kmask = jnp.arange(STATIC_K)[None, None, :] < karr
    cum = jnp.cumsum(probs, axis=-1)
    pmask = (cum - probs) < top_p[:, None, None]
    mask = kmask & pmask
    masked = jnp.where(mask, scaled, -jnp.inf)                    # [B,T,Kw]
    win_p = jax.nn.softmax(masked, axis=-1)

    # draft-token probability under the target sampling distribution; a
    # draft outside the masked window has p=0 and is always rejected (the
    # non-spec sampler could never have emitted it)
    in_win = (idxs[:, :K] == drafts[:, :, None]) & mask[:, :K]    # [B,K,Kw]
    p_draft = jnp.sum(jnp.where(in_win, win_p[:, :K], 0.0), -1)   # [B,K]
    resid = jnp.where(in_win, -jnp.inf, masked[:, :K])            # [B,K,Kw]

    # per-lane subkeys: T full draws + K residual draws + 1 uniform vector
    sub = jax.vmap(lambda k: jax.random.split(k, T + K + 2))(key)
    new_keys = sub[:, 0]
    cat = jax.vmap(jax.vmap(jax.random.categorical))
    full_w = cat(sub[:, 1:1 + T], masked)                         # [B,T]
    resid_w = cat(sub[:, 1 + T:1 + T + K], resid)                 # [B,K]
    full_tok = jnp.take_along_axis(idxs, full_w[..., None], -1)[..., 0]
    resid_tok = jnp.take_along_axis(
        idxs[:, :K], resid_w[..., None], -1)[..., 0]
    u = jax.vmap(lambda k: jax.random.uniform(k, (K,)))(sub[:, T + K + 1])

    # logprobs are reported from the UNSCALED post-penalty distribution,
    # matching sample()'s contract
    def lp_at(tok):
        return jnp.take_along_axis(
            logp_all[:, :tok.shape[1]], tok[..., None].astype(jnp.int32),
            axis=-1)[..., 0]

    packed = jnp.concatenate([
        greedy.astype(jnp.float32), logp_greedy,
        full_tok.astype(jnp.float32), lp_at(full_tok),
        resid_tok.astype(jnp.float32), lp_at(resid_tok),
        lp_at(drafts), p_draft, u.astype(jnp.float32),
    ], axis=1)
    return packed, new_keys


def spec_unpack(packed: np.ndarray, K: int) -> Dict[str, np.ndarray]:
    """Split the packed verify fetch back into named host arrays [B, ...]."""
    T = K + 1
    cuts = {"greedy_tok": T, "logp_greedy": T, "full_tok": T,
            "logp_full": T, "resid_tok": K, "logp_resid": K,
            "logp_draft": K, "p_draft": K, "u": K}
    out: Dict[str, np.ndarray] = {}
    off = 0
    for name, w in cuts.items():
        out[name] = packed[:, off:off + w]
        off += w
    return out


def spec_accept(drafts: List[int], is_greedy: bool, lane: Dict[str, np.ndarray]
                ) -> Tuple[List[int], List[float], int]:
    """Host-side acceptance for ONE lane. ``lane`` holds that lane's rows of
    :func:`spec_unpack`'s arrays. Returns (tokens, token_logprobs,
    n_accepted_drafts); between 1 and len(drafts)+1 tokens are emitted.

    Greedy: accept drafts while they match argmax; the emitted token at the
    first mismatch IS the argmax (what non-spec decode would have produced),
    so greedy output is token-identical to the non-speculative path.
    Temperature>0: accept draft i iff u_i < p(d_i); on rejection emit the
    residual-distribution token; if every draft is accepted, emit one bonus
    token sampled from the full target distribution at the next position."""
    toks: List[int] = []
    lps: List[float] = []
    acc = 0
    for i, d in enumerate(drafts):
        if is_greedy:
            tgt = int(lane["greedy_tok"][i])
            toks.append(tgt)
            lps.append(float(lane["logp_greedy"][i]))
            if tgt != int(d):
                return toks, lps, acc
            acc += 1
        elif float(lane["u"][i]) < float(lane["p_draft"][i]):
            toks.append(int(d))
            lps.append(float(lane["logp_draft"][i]))
            acc += 1
        else:
            toks.append(int(lane["resid_tok"][i]))
            lps.append(float(lane["logp_resid"][i]))
            return toks, lps, acc
    j = len(drafts)
    if is_greedy:
        toks.append(int(lane["greedy_tok"][j]))
        lps.append(float(lane["logp_greedy"][j]))
    else:
        toks.append(int(lane["full_tok"][j]))
        lps.append(float(lane["logp_full"][j]))
    return toks, lps, acc

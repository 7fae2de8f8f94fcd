"""The serving engines' sampler, inside the compiled decode step: greedy or
temperature / top-k / top-p by each batch row's own params and PRNG key.
"""


def _sampler_path(samplings) -> str:
    """The sampler's path for a chunk whose occupants ask for `samplings`,
    by the rule the device applies to its live rows: `greedy` (the
    argmax-only program), `sort` (an occupant that samples has a nucleus,
    `top_p` < 1: one sort of the vocabulary a step) or `select`."""
    sampled = [s for s in samplings if s.temperature > 0.0]
    if not sampled:
        return "greedy"
    return "sort" if any(s.top_p < 1.0 for s in sampled) else "select"


def _kth_largest(x, k):
    """The k-th largest value of each row of x [B, V] float32, k [B] in
    1..V: exact, ties and all, without an order. The floats' bits, read as
    ordered integers, are searched from the top bit down: 32 passes that
    each count a row's values at or above a candidate (on the v5e 0.03-0.08
    ms together at the serving shapes, where `lax.top_k` of 64 or 128
    candidates is a `TopK` call that costs 0.87 of a full sort: PERF.md
    section 6, PR 35). Written out, not a loop: the decode step keeps no
    loop of its own (PERF.md section 7 (l))."""
    import jax.numpy as jnp
    from jax import lax

    bits = lax.bitcast_convert_type(x, jnp.int32)
    # a negative float's bits fall as it rises: flip them; lift the others
    # above them by the sign bit
    image = lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits | jnp.int32(-2**31)), jnp.uint32)
    found = jnp.zeros((x.shape[0], 1), jnp.uint32)
    for bit in range(31, -1, -1):
        candidate = found | jnp.uint32(1 << bit)
        at_or_above = jnp.sum(image >= candidate, axis=-1, keepdims=True)
        found = jnp.where(at_or_above >= k[:, None], candidate, found)
    found = lax.bitcast_convert_type(found, jnp.int32)
    return lax.bitcast_convert_type(
        jnp.where(found < 0, found & jnp.int32(2**31 - 1), ~found),
        jnp.float32)


def _kept_logits(logits, temp, top_k, top_p, live=None):
    """The scaled logits [B, V] a row draws from, -inf where a token is not
    kept: a row keeps every token whose scaled logit is >= its k-th largest
    (ties included), and of those every token whose probability is >= the
    smallest of the shortest prefix whose mass reaches top_p.

    Which path a step takes is read from its own inputs. No order is taken
    that no live sampling row asks for: without a nucleus in any of them the
    k-th values are SELECTED (`_kth_largest`), or nothing is masked at all
    where no row has a top_k either; a nucleus needs the kept values in
    order, and the step sorts the vocabulary ONCE (the sorted probabilities
    are the softmax of the sorted logits: softmax is monotone)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    vocab = logits.shape[-1]
    lt = logits / jnp.maximum(temp, 1e-6)[:, None]
    sampled = temp > 0.0 if live is None else (temp > 0.0) & live
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, vocab), 1, vocab)
    by_k = sampled & (k_eff < vocab)  # rows whose top-k masks anything
    by_p = sampled & (top_p < 1.0)

    def below(kth):
        return jnp.where(by_k[:, None] & (lt < kth), -jnp.inf, lt)

    @jax.named_scope("select")
    def select():
        return lax.cond(jnp.any(by_k),
                        lambda: below(_kth_largest(lt, k_eff)), lambda: lt)

    @jax.named_scope("nucleus")
    def nucleus():
        ordered = jnp.sort(lt, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(ordered, (k_eff - 1)[:, None], axis=-1)
        lt_k = below(kth)
        top = jnp.max(lt_k, axis=-1, keepdims=True)
        e = jnp.exp(lt_k - top)
        mass = jnp.sum(e, axis=-1, keepdims=True)
        # the sorted probabilities, without sorting them
        sp = jnp.where(by_k[:, None] & (ordered < kth), 0.0,
                       jnp.exp(ordered - top) / mass)
        csum = jnp.cumsum(sp, axis=-1)
        # smallest prefix whose mass reaches top_p (always keeps the top
        # token: csum - sp is 0 for it)
        keep = (csum - sp) < top_p[:, None]
        min_keep = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1,
                           keepdims=True)
        return jnp.where(by_p[:, None] & (e / mass < min_keep), -jnp.inf,
                         lt_k)

    return lax.cond(jnp.any(by_p), nucleus, select)


def token_prob(logits, token):
    """softmax(logits)[token] a row, float32: logits [B, V] (-inf where a
    token is not kept), token [B]."""
    import jax
    import jax.numpy as jnp

    at = jnp.take_along_axis(logits, token[:, None], axis=-1)[:, 0]
    return jnp.exp(at - jax.nn.logsumexp(logits, axis=-1))


def _make_sampler(vocab: int):
    import jax
    import jax.numpy as jnp

    @jax.named_scope("sampler")  # its name in a device trace
    def sample(logits, keys, temp, top_k, top_p, live=None,
               with_prob: bool = False):
        """logits [B, V] f32; keys [B, 2] uint32; temp/top_k/top_p [B];
        live [B] bool, the rows somebody reads (all of them without it).
        temp <= 0 -> greedy. top_k <= 0 -> disabled. top_p >= 1 -> disabled.
        The draw is `categorical` over `_kept_logits`. `with_prob` (a block
        step's rows, B slots x L positions): also the drawn token's
        probability under the distribution it was drawn from, the kept,
        tempered one, and for a greedy row the argmax's under the plain
        softmax; (tokens, probabilities [B] float32)."""
        assert logits.shape[-1] == vocab
        greedy = jnp.argmax(logits, axis=-1)
        kept = _kept_logits(logits, temp, top_k, top_p, live)
        drawn = jax.vmap(jax.random.categorical)(keys, kept)
        token = jnp.where(temp <= 0.0, greedy, drawn).astype(jnp.int32)
        if not with_prob:
            return token
        return token, token_prob(
            jnp.where((temp <= 0.0)[:, None], logits, kept), token)

    return sample

"""``sampling.draw_slots`` does only what some live row asks for: every
mix of greedy, sampled and filtered rows draws, bit for bit, what a
row-by-row ``draw`` and the unconditional formula (sort, softmax,
cumsum and gumbel draw for every row, whatever it asked for) draw; and
the sort, the cumsum and the random bits sit inside a branch of the
program, never at its top level."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import sampling

VOCAB = 97
ROWS = 6


def _unconditional(logits, keys, t, temperature, top_k, top_p, masks=None):
    """The draw as it was before it chose how much to run: the oracle."""

    def one(lg, key, tt, temp, kk, pp, mask=None):
        if mask is not None:
            lg = jnp.where(mask, lg, jnp.finfo(lg.dtype).min)
        safe = jnp.where(temp > 0, temp, jnp.float32(1.0))
        scaled = sampling._filter_logits_traced(lg / safe, kk, pp)
        sampled = jax.random.categorical(
            jax.random.fold_in(key, tt), scaled, axis=-1)
        greedy = jnp.argmax(lg, axis=-1)
        return jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)

    args = (logits[:, None], keys, t, temperature, top_k, top_p)
    if masks is not None:
        args += (masks[:, None],)
    return jax.vmap(one)(*args)[:, 0]


def _batch(temps, top_ks, top_ps, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = jax.random.normal(k1, (ROWS, VOCAB)) * 3.0
    keys = jnp.stack([jnp.asarray(jax.random.PRNGKey(40 + i), jnp.uint32)
                      for i in range(ROWS)])
    # a mask that drops a fifth of the vocabulary, the argmax of the
    # first row among it, and leaves the last row unconstrained
    masks = jax.random.uniform(k2, (ROWS, VOCAB)) > 0.2
    masks = masks.at[0, jnp.argmax(logits[0])].set(False)
    masks = masks.at[-1].set(True)
    return (logits, keys, jnp.asarray([3, 5, 0, 9, 2, 7], jnp.int32),
            jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32), masks)


#: name: (temperature, top_k, top_p, live, the level the live rows ask for)
CASES = {
    "all_greedy": ([0.0] * 6, [0, 5, 0, 3, 0, 0],
                   [1.0, 1.0, 0.6, 0.9, 1.0, 1.0], None, 0),
    "sampled_filters_off": ([0.7, 1.3, 0.0, 1.0, 0.5, 2.0],
                            [0, VOCAB, 0, VOCAB + 9, -1, 0],
                            [1.0, 1.0, 0.3, 0.0, 1.5, 1.0], None, 1),
    "top_k_only": ([0.7, 1.3, 1.0, 1.0, 0.5, 2.0], [5, 0, 1, VOCAB - 1, 0, 3],
                   [1.0] * 6, None, 2),
    "top_p_only": ([0.7, 1.3, 1.0, 1.0, 0.5, 2.0], [0] * 6,
                   [0.9, 1.0, 0.05, 0.5, 0.999, 1.0], None, 2),
    "top_k_and_top_p": ([0.7, 1.3, 1.0, 1.0, 0.5, 2.0], [5, 40, 0, 3, 2, 0],
                        [0.9, 0.6, 0.3, 1.0, 0.5, 1.0], None, 2),
    "mixed_greedy_and_filtered": ([0.0, 0.8, 0.0, 0.0, 1.2, 0.0],
                                  [0, 0, 4, 0, 7, 0],
                                  [1.0, 0.9, 0.5, 1.0, 1.0, 1.0], None, 2),
    "filtered_row_is_done": ([0.0, 0.8, 0.0, 0.0, 0.0, 0.0],
                             [0, 3, 0, 0, 0, 0],
                             [1.0, 0.9, 1.0, 1.0, 1.0, 1.0],
                             [True, False, True, True, True, True], 0),
    "sampled_row_is_done_beside_an_unfiltered_one": (
        [0.0, 0.8, 0.0, 0.6, 0.0, 0.0], [0, 3, 0, 0, 0, 0],
        [1.0, 0.9, 1.0, 1.0, 1.0, 1.0],
        [True, False, True, True, True, False], 1),
}


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "masked"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_draw_slots_draws_what_each_row_alone_and_the_old_formula_draw(
        case, masked):
    temps, top_ks, top_ps, live, level = CASES[case]
    logits, keys, t, temp, kk, pp, masks = _batch(temps, top_ks, top_ps)
    masks = masks if masked else None
    live_rows = np.ones(ROWS, bool) if live is None else np.asarray(live)
    got = np.asarray(jax.jit(sampling.draw_slots)(
        logits, keys, t, temp, kk, pp, masks,
        None if live is None else jnp.asarray(live)))
    want = np.asarray(_unconditional(logits, keys, t, temp, kk, pp, masks))
    np.testing.assert_array_equal(got[live_rows], want[live_rows])
    for i in np.flatnonzero(live_rows):
        alone = sampling.draw(
            logits[i:i + 1], int(t[i]), temperature=temps[i],
            top_k=top_ks[i], top_p=top_ps[i], key=keys[i],
            mask=None if masks is None else masks[i:i + 1])[0]
        assert int(got[i]) == int(alone), f"row {i}"
    # the level the program took shows in the rows nobody reads: at a
    # level under the one its stale parameters ask for, a dead row
    # gets what that lower level draws
    masked_logits = logits if masks is None else jnp.where(
        masks, logits, jnp.finfo(logits.dtype).min)
    if level == 0:
        np.testing.assert_array_equal(
            got, np.asarray(jnp.argmax(masked_logits, axis=-1)))
    elif level == 1:
        off = jnp.zeros_like(kk)
        np.testing.assert_array_equal(got, np.asarray(_unconditional(
            logits, keys, t, temp, off, off.astype(jnp.float32), masks)))


def _primitives(jaxpr, into_cond=True):
    """Primitive names of a jaxpr and of every jaxpr nested in it
    (``pjit``, ``custom_jvp``: counted where they are called); with
    ``into_cond`` False, a ``cond``'s branches are left out."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if into_cond or eqn.primitive.name != "cond":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                names += _primitives(sub, into_cond)
    return names


@pytest.mark.parametrize("with_live", [False, True], ids=["all", "live"])
def test_sort_cumsum_and_random_bits_sit_inside_a_branch(with_live):
    logits, keys, t, temp, kk, pp, masks = _batch(
        *CASES["top_k_and_top_p"][:3])
    live = jnp.ones((ROWS,), bool) if with_live else None
    jaxpr = jax.make_jaxpr(sampling.draw_slots)(
        logits, keys, t, temp, kk, pp, masks, live).jaxpr
    top = _primitives(jaxpr, into_cond=False)
    everywhere = _primitives(jaxpr)
    costly = ("sort", "cumsum", "random_bits", "random_fold_in", "exp",
              "reduce_sum")
    assert top.count("cond") == 1
    for name in ("sort", "cumsum", "random_bits", "random_fold_in"):
        assert name in everywhere, name
    for name in costly:
        assert name not in top, name
    # the branches, in the order of the level: the first holds none of
    # the costly work, the second the draw without the sort
    cond = next(e for e in jaxpr.eqns if e.primitive.name == "cond")
    per_branch = [_primitives(b.jaxpr) for b in cond.params["branches"]]
    assert len(per_branch) == 3
    assert not set(per_branch[0]) & set(costly)
    assert "random_bits" in per_branch[1]
    assert not {"sort", "cumsum"} & set(per_branch[1])
    assert {"sort", "cumsum", "random_bits"} <= set(per_branch[2])

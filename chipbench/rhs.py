"""Right-hand sides, made on the device from the seed, one per solve.

A traffic file names their kind (``"rhs": "normal"``: standard normal,
float32) and how the cases follow one another. Case ``c`` is the vector
drawn from the key ``fold_in(base, c)``. The base key comes from the
run's ``--seed``, or, where the traffic names a ``case_seed``, from that,
so that every run solves the same cases. Solve ``i`` takes case
``order[i]`` while ``i < ordered_cases``, ``order`` being a permutation
drawn from the run's seed, and case ``i`` after that: no case repeats in
a run, however many solves the window completes, and where every run's
cases are the same they come in another order for each seed.
"""

from __future__ import annotations

import numpy as np

from chipbench.spec import SpecError


def seed_words(seed: int, stream: int):
    """Two 32-bit words derived from (seed, stream), for any whole seed."""
    return np.random.SeedSequence([int(seed), stream]).generate_state(2)


class Cases:
    """The case stream of one run: :meth:`case` maps a solve's position
    to its case, :meth:`make` puts a case on the device, and :meth:`warm`
    gives a vector of the same shape that no case of the window uses."""

    def __init__(self, jax, traffic, seed: int, rows: int):
        import jax.numpy as jnp
        if traffic.get("rhs") != "normal":
            raise SpecError("unknown right-hand side kind %r"
                            % traffic.get("rhs"))
        fixed = traffic.get("case_seed")

        def key(words):
            return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                                      int(words[1]))

        self._base = key(seed_words(seed if fixed is None else fixed, 0))
        self._warm = key(seed_words(seed, 3))
        k = int(traffic.get("ordered_cases", 0))
        self._order = np.random.default_rng(seed_words(seed, 2)) \
            .permutation(k) if k else np.zeros(0, int)

        @jax.jit
        def make(base, c):
            return jax.random.normal(jax.random.fold_in(base, c), (rows,),
                                     jnp.float32)

        self._make = make

    def case(self, i: int) -> int:
        return int(self._order[i]) if i < len(self._order) else i

    def make(self, c: int):
        return self._make(self._base, np.uint32(c))

    def warm(self):
        return self._make(self._warm, np.uint32(0))

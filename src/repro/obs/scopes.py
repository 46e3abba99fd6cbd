"""Names of the HFL stages on the device.

Every compiled round carries ``jax.named_scope`` markers, so the ops of
each stage keep the stage's name in their HLO ``op_name`` metadata and
a device profile can split its time by stage (``bench/stages.py``). A
scope changes metadata only: no op, no runtime cost. The fixed ``hfl.``
prefix keeps the names apart from every name JAX generates.

=================  =======================================================
``hfl.env``        Eq. 4-6 realisation: draws, ``context_pairwise``,
                   outcomes, contexts (``sim.core.sim_round``,
                   ``round_batch``, ``init_statics``)
``hfl.select``     ``policy.select`` + ``policy.update``: the CC-MAB
                   index, the density sort, the budget walk
``hfl.walk``       the budget walk's ``while_loop`` alone, inside
                   ``hfl.select`` (``greedy_walk``, ``flgreedy_walk``
                   and the ``greedy_assign`` / ``flgreedy_assign`` loops)
``hfl.train``      packing, minibatch indices and gathers, the
                   slot-parameter broadcast, Eq. 2 local SGD
``hfl.aggregate``  corruption scaling, the deadline mask, Eq. 3
                   aggregation and the ``t_ES`` global sync
``hfl.eval``       the block-end test-set evaluation
=================  =======================================================
"""
from __future__ import annotations

PREFIX = "hfl."
ENV = PREFIX + "env"
SELECT = PREFIX + "select"
WALK = PREFIX + "walk"
TRAIN = PREFIX + "train"
AGGREGATE = PREFIX + "aggregate"
EVAL = PREFIX + "eval"

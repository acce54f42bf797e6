"""What a prefill step and a decode step cost on this model, as the
incremental loop itself measures them.

``RequestManager.generate_incr_decoding`` prefills, each scheduler round,
as many steps as everyone resident pays for: ``steps x p x decoding <=
block x d x (decoding + filling)``, one step always. The row-seconds a
round's prefill stalls the rows decoding (``decoding x steps x p``) may
reach the request-seconds the decode block takes from everyone in a slot:
the rows that ride it (``decoding x block x d``, PR 32's bound) and the
requests still filling that it holds off (``filling x block x d``, PR
36's, which took the larger of the two terms where this is their sum). At
a full batch (``filling`` 0) the steps of one round together may take as
long as the block that follows them and no longer, so a decoding row
waits for prefill at most one block's time; a batch that has emptied
earns its refill in proportion to how empty it is. The quotient ``block x
d / p`` falls with every gain in the decode step, and under the larger-of
rule the batch fell with it, in whole steps, wherever the decoders stayed
the majority (K-EXAONE's queue after PR 38 and 43: 18 of 32 rows
decoding, 14 residents outside the block, weight 1). Under the sum, with
every slot resident and ``k`` steps a row a round needed, the rows settle
where ``k x rows = quotient x slots / rows``: at the geometric mean of the
slots and of what the larger-of rule held, moving with the square root of
the decode step's cost and not with the quotient's floor (PERF.md section
6, PR 48). Both need the two programs' cost on the model being served, and
nothing states it ahead of time: a prefill step is 1.2 decode steps of
OLMoE, 2.8 of K-EXAONE at PR 32 and 4.7 at PR 47, 3.4-3.9 of Falcon, 15 of
Mistral-4 at 3 rows (PERF.md section 6, PR 32, 36 and 48).

A prefill step is dispatched without a fence and the decode block's
readback fences both, so a round's wall time does not say which program
took it. Every ``EVERY``-th round that prefills is therefore TIMED: the
loop waits for each of the round's prefill steps before it stages the
next (a few ms of lost overlap a step), and gets a sample of each cost.
Any other round's first step is launched behind the decode block before
it, while that block runs (its LEAD step); ``due`` is asked where that
step would be launched, and a round that is to be timed takes none, so
it starts on an idle device.
Telemetry or not: with it on the wait is on the step's own output and
with it off a fence of the state, and every other round's steps queue
behind each other either way, so the traced run has the policy and the
staging of the untraced. What a timed round measures is a step run alone
and waited for: a little more than the step costs the device when the
next one is staged behind it, so the bound is kept with room. A round that
prefills nothing gives a decode sample for free. Each estimate is the
median of its last ``KEEP`` samples, so a compile or a stop of the machine
(0.1-10 s at times) inside one sample moves nothing, and there is no
estimate until ``MIN`` samples are in.
"""

from __future__ import annotations

import statistics
from collections import deque


class StepCosts:
    KEEP = 5        # samples an estimate is the median of
    MIN = 3         # samples before there is an estimate
    EVERY = 8       # of the rounds that prefill, one in EVERY is timed

    def __init__(self):
        self._prefill = deque(maxlen=self.KEEP)     # seconds a step
        self._decode = deque(maxlen=self.KEEP)      # seconds a step
        self._rounds = 0

    def due(self) -> bool:
        """Asked once for each round that prefills, where its first step
        is about to be launched: whether to time it. Every one until both
        estimates stand, then one in ``EVERY``."""
        self._rounds += 1
        return (min(len(self._prefill), len(self._decode)) < self.MIN
                or self._rounds % self.EVERY == 0)

    def note_prefill(self, seconds: float, steps: int):
        """``steps`` prefill steps, each waited for, took ``seconds``."""
        self._prefill.append(seconds / steps)

    def note_decode(self, seconds: float, steps: int):
        """A decode block of ``steps`` took ``seconds`` on an idle device."""
        self._decode.append(seconds / steps)

    @staticmethod
    def weight(decoding: int, filling: int) -> float:
        """Everyone resident over the rows decoding: what a round's decode
        block is worth in prefill, in blocks."""
        return (decoding + filling) / decoding

    def allowance(self, block_steps: int, decoding: int, filling: int) -> int:
        """The prefill steps a round may take before its decode block of
        ``block_steps`` for ``decoding`` rows, with ``filling`` requests
        in slots still short of their prompts: as many as together cost
        no more than the block, times everyone resident over the rows
        decoding. One always; one alone while either cost is still
        unknown."""
        if min(len(self._prefill), len(self._decode)) < self.MIN:
            return 1
        block_s = block_steps * statistics.median(self._decode)
        return max(1, int(block_s * self.weight(decoding, filling)
                          / statistics.median(self._prefill)))


class GivenCosts(StepCosts):
    """The two costs as given and never timed, for a test or a check that
    wants the same steps a round on every machine: set it as the
    InferenceManager's ``step_costs`` before the loop runs."""

    def __init__(self, prefill_s: float, decode_step_s: float):
        super().__init__()
        self._prefill.extend([prefill_s] * self.MIN)
        self._decode.extend([decode_step_s] * self.MIN)

    def due(self) -> bool:
        return False

    def note_prefill(self, seconds: float, steps: int):
        pass

    note_decode = note_prefill

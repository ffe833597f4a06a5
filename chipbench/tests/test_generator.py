"""The open-loop generator: every seed offers the same work in each run
of ``shuffle_block`` arrivals, in another order."""

from collections import Counter

from common import BENCH, load_json
from generator import schedule


def blocks(arrivals, block):
    """Per phase, the multiset of (prompt, output) lengths and of gaps in
    each run of ``block`` arrivals."""
    out = []
    for phase in ("ramp", "window", "tail"):
        got = [a for a in arrivals if a.phase == phase]
        for lo in range(0, len(got), block):
            run = got[lo:lo + block]
            out.append(Counter((len(a.prompt), a.max_new_tokens)
                               for a in run))
    return out


def test_every_seed_offers_the_same_work_in_each_block():
    mix = load_json(BENCH / "traffic" / "short_chat.json")
    block = mix["shuffle_block"]
    one = schedule(mix, 50288, 2 ** 33 + 1, 51.0)
    two = schedule(mix, 50288, 2 ** 31 + 7, 51.0)
    assert blocks(one, block) == blocks(two, block)
    assert [a.max_new_tokens for a in one] != \
        [a.max_new_tokens for a in two]
    assert [a.at_s for a in one] != [a.at_s for a in two]
    for arrivals in (one, two):
        window = [a.at_s for a in arrivals if a.phase == "window"]
        assert len(window) == round(mix["rate_per_s"] * 51)
        assert mix["ramp_s"] <= min(window) and max(window) < \
            mix["ramp_s"] + 51

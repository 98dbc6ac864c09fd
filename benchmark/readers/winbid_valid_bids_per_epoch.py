"""Operators, from inside: bids that counted at the ``winning`` vertex of
the ``nexmark-average-price`` job — inside their auction's ``[dateTime,
expires)`` and at or over its reserve — per committed epoch over the whole
run: the program's ``winbid.valid_bids.winning`` counter. What a bid
delivered twice upstream of the join moves though the rows do not (a
maximum takes a duplicate in silence, a mean of doubled rows does not
move): the reference counts the same bids (``Want.valid``), and
``tests/test_average_price.py`` holds the two equal. None on a program
that keeps no such counter."""

from benchlib import program_spans


def read(run):
    bids = program_spans.of(run).counters.get("winbid.valid_bids.winning")
    if bids is None or not run.stamps:
        return None
    return bids / len(run.stamps)

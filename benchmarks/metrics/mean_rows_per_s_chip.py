"""All input rows of the window's completed pairs over the time from
the window's opening to the last pair's end, per chip: the rate over
all the work and all the time.  One stalled job moves it by a tenth
(PERF.md, Findings, PR 23) and the medians that decide by nothing, so
it is recorded here, where its spread can be seen, until a benchmark PR
can name the stall and promote it."""


def read(trace, spans, counters, cell):
    done = [rec for rec in spans["pairs"] if "pair_s" in rec]
    if not done:
        return None
    return cell.pair_rows * len(done) / max(r["end"] for r in done) / cell.chips

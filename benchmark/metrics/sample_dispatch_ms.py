"""Host time to get a batch on its way: per iteration, the program's spans
`buffer/sample` (the sampler's index draw and the gather's dispatch) and
`buffer/stage` (`stage_batch`). Median over the window's iterations."""

from ..reduce import spans

SPANS = ("buffer/sample", "buffer/stage")


def read(run: dict):
    return spans.median_ms(run, *SPANS)

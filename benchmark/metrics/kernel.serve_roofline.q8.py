from benchmark.readers import serve_roofline_pct


def read(run):
    """The least time the configuration's demand (``int8_two_stage_q8.py``:
    the codes streamed once, the survivors gathered) allows a dispatch of the
    window's mean batch, over the device time a dispatch took."""
    return serve_roofline_pct(run)

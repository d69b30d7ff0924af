"""Seconds of the set-up's live stretch: the newest scrapes through the
coordinator's writer (`DownsamplerAndWriter.write_batch`, 500-row requests) with
the embedded downsampler flushed at every minute's end, on the set-up's clock."""


def read(m):
    return m.setup.get("downsample_live_s")

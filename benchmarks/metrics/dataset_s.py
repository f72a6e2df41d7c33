"""Host clock around lgb.Dataset(...).construct(): sample, bin bounds,
device binning, download and packing."""


def read(ctx):
    return ctx.get("dataset_s")

"""Host seconds of ``RoundEngine(...)``: visibility, delay and contact
tables, data set and partition, client plane."""


def read(ctx):
    return ctx.engine_build_s

"""The served overlay's largest-component diameter right after the
window, ms (``GET /v1/diameter?exact=1``): the quantity DGRO exists to
reduce.  It is checked against the reference in every run."""


def read(ctx):
    return ctx.overlay_diameter

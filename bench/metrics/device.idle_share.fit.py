"""Percent of the traced fits in which no operation ran on a device,
averaged over the devices used."""


def read(ctx):
    return ctx["idle_share"]()

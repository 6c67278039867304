"""Percent of the traced serving window in which no operation ran on the
device."""


def read(ctx):
    return ctx["idle_share"]()

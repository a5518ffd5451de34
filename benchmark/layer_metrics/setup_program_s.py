"""Compile layer: the program's share of set-up. Wall seconds inside
the session's public entry points before the window (``parallel_run``,
``prepare``, ``warmup``, ``run``; the outermost call only), which the
program adds into its float counter ``startup.api_s``, read off the
registry as the kind's loop found it when set-up ended. ``setup_s``
less this is the benchmark's own share: the builder's Python and
imports, the generator, the static checks, a builder's own jits."""


def read(ctx):
    return ctx.run["registry_before"].get("startup.api_s")

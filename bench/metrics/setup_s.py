"""Seconds from process start to the window's first request: server
construction, prewarm (compiles or compile-cache loads), warm-up
traffic."""


def read(ctx):
    return ctx["setup_s"]

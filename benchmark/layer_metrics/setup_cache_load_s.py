"""Compile layer: the part of ``setup_backend_compile_s`` spent reading
the persistent cache's entries and deserialising them
(``compile.cache_retrieval_s`` when set-up ended). From a warm cache it
is nearly all of it, and it grows with the serialized step."""


def read(ctx):
    return ctx.run["registry_before"].get("compile.cache_retrieval_s")

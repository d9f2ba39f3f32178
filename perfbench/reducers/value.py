"""A number the harness itself measured (values[key])."""


def reduce(ctx, key):
    return ctx["values"].get(key)

"""Mean recall@10 of every judged answer, against the plain reference."""


def read(ctx):
    return ctx.numbers["recall"] if ctx.numbers["rows"] else None

"""Output tokens emitted in the window over the window's length."""
import endtoend


def read(ctx):
    return endtoend.output_tok_s(ctx.window)

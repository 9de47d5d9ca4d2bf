"""Programs JAX compiled between the snapshot after warm-up and the
end of the window (``Compiles`` of harness/server.py).  Should read 0;
``run.py --log-compiles`` names them."""


def read(ctx: dict, args: dict):
    before, after = ctx["compiles"]
    return after["n"] - before["n"]

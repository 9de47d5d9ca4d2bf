"""Programs JAX compiled between the snapshot after warm-up and the
end of the window (``Compiles`` of harness/server.py).  Should read 0;
running with ``JAX_LOG_COMPILES=1`` names them."""


def read(ctx: dict, args: dict):
    before, after = ctx["compiles"]
    return after["n"] - before["n"]

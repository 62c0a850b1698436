"""Messages waiting at a teller, the mean over the tellers of `tail -
head` at the window's last segment end (read outside the segments'
clock): how far the coordinator lags its replies."""


def read(ctx):
    return ctx["window"].get("teller_depth")

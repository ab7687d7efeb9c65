"""One module per model family: how the harness builds that family out of
the program, feeds it, and which plain reference it is held to."""

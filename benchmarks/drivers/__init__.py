"""One module per kind of run (``train``, ``decode``): it sets the
cell up, drives the measured window, and checks what the window produced."""

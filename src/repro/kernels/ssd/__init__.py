"""Mamba-2 SSD recurrence: the (head_dim, d_state) state of every head in
VMEM across a chunk (``kernel``) or a decode window (``decode``)."""

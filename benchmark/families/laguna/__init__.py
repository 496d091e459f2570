"""The ``laguna`` family: Laguna-XS.2's block (window and full attention at
a head count and a rotary embedding of their own, a gate a head on the
attention output, a shared expert beside the held ones) as a token-window
Q-network."""

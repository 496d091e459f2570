"""The ``moonlight`` family: Moonlight-16B-A3B's block (latent attention, a
shared expert beside the held ones) as a token-window Q-network."""

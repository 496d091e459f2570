"""The ``sdar`` family: SDAR-30B-A3B-Chat's block (generation by diffusion
over blocks: the window run twice under a three-part block mask, one
decision a block) as a token-window Q-network."""

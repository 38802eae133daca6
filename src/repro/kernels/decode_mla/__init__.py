"""Decode attention over a latent (MLA) store: MQA over latent rows."""

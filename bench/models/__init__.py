"""One module per served architecture, named by a configuration file's
``"model"`` key. Each gives ``bench/drivers/serve.py`` the same five
functions: ``program_config(doc)``, ``shapes(doc)``,
``program_params(cfg, sh, seed)``, ``counts(cfg)`` and
``token_gaps(sh, seed, seqs, control=...)``."""

"""Launch layer: the serving CLI (``serve``) and the compile-cache setup
(``cache``) that the entry points share."""

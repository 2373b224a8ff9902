"""The benchmark's harness: everything a cell's run is made of except the
system under test.  Nothing here touches jax at import."""
